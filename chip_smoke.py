"""Chip smoke test: the served prefill path on a TPU at a supported model's
full width.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip pipeline only

One chip: qwen3-8b at its published widths (d_model 4096, 32 q / 8 kv heads,
head_dim 128, d_ff 12288, vocab 151936, bf16) cut to 8 of 36 layers, built by
the same engine builder ``repro.launch.serve`` uses, serving 4 requests of
16384 tokens (8 chunks of 2048) through the Pallas attention kernels with
the paged pool kernel. The first wave compiles; the later waves are timed.
Parity: the served logits against the same pipeline under the jnp attention
reference on the same tokens, and a 2048-token pipeline run against the
plain full-sequence ``model.forward``, beside the jnp pipeline against that
forward (the bf16 floor).

Four chips: full-depth qwen3-8b (36 layers, 9 per stage) served through the
same builder as a 4-stage MOCAP pipeline (MBKR on, seq 32768 in 16 chunks),
against the same pipeline with MBKR off (Terapipe-style) on the same tokens,
and an MBKR run that spills the last 4 chunks so that later chunks attend
spilled ones remotely (qship); its wire bytes must match the wire model,
and its logits must agree with Terapipe's, and with the same spill run
under the jnp reference, within the bf16 floor at full depth (Terapipe
under the Pallas kernels against Terapipe under jnp). Per-device memory
shows the stages spread over the chips.

Weights are random, made from a seed. Without a TPU the script exits
non-zero and prints no result. The last line of standard output is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import replace as dc_replace

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.configs.base import get_config, replace  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.options import ServeOptions  # noqa: E402

# Relative L2 error bound of next-token logits between two runs of the same
# bf16 weights on the 8-layer cut. The compared paths differ in attention
# blocking, softmax accumulation order and chunking, so their bf16
# roundings differ and add up over the layers: the jnp pipeline against
# model.forward (the bf16 floor) reads 1.69e-2 on a TPU v5e. The bound is
# about twice that floor, and far below the ~1.4 of two unrelated logit
# vectors.
LOGITS_RTOL = 3e-2

# The same bound at full depth (36 layers, the four-chip phase): if the
# per-layer roundings add up as a random walk, the error grows with the
# square root of depth, sqrt(36 / 8) = 2.1 times the 8-layer bound. The
# floor there (Terapipe under Pallas against jnp) reads 3.43e-2 on four
# TPU v5e chips.
DEEP_LOGITS_RTOL = 6.4e-2

# The Pallas pipeline may differ from ``model.forward`` by at most this many
# times what the jnp pipeline does on the same tokens (the bf16 floor): the
# kernels' own roundings (p cast to bf16 before the PV matmul, blocked
# online softmax) are of the same kind and number as the jnp path's, while a
# kernel fault (a wrong mask, a stale page, a dropped block) gives an error
# of order 1.
FLOOR_FACTOR = 2.0

# Chunks per request the four-chip check spills to the pair stage. At N=4,
# M=16 the fewest-slots plan spills only the last chunk, which no later
# chunk reads; spilling 4 (p2=12) makes chunks 13-15 attend spilled chunks
# through the remote (qship) path.
SPILL_CHUNKS = 4


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _log(msg: str) -> None:
    print(msg, flush=True)


def _require(ok, what) -> None:
    """A failed check fails the run (an ``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _ready(x):
    import jax
    return jax.block_until_ready(x)


def _assert_mosaic(fn, *args) -> None:
    """The lowered program holds compiled Pallas kernels, not interpret-mode
    loops."""
    text = fn.lower(*args).as_text()
    _require("tpu_custom_call" in text, "no Mosaic kernel in the lowered step")


def _check_logits(name: str, got, ref) -> float:
    err = rel_err(got, ref)
    _log(f"[parity] {name}: rel L2 err {err:.3e} (limit {LOGITS_RTOL:.0e})")
    _require(np.isfinite(err) and err < LOGITS_RTOL, (name, err))
    return err


def _serve(cfg, opts: ServeOptions):
    """Build the engine the way ``serve`` does and serve ``opts.requests``
    prompts: (ec, engine, requests, finished, timings)."""
    t0 = time.perf_counter()
    cfg, ec, eng = serve._build_engine(opts, cfg=cfg)
    _ready(eng.executor.staged)
    _log(f"[build] params {cfg.param_count() / 1e9:.3f} B in "
         f"{time.perf_counter() - t0:.3f} s | stages {ec.num_stages} "
         f"tp {ec.tp} | hw {ec.hw.name}")
    reqs = serve._make_requests(opts, cfg.vocab_size)
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    done = sorted(eng.poll(), key=lambda r: r.rid)
    _require([r.rid for r in done] == list(range(opts.requests)),
             "every request served")
    for r in done:
        _require(r.result.ndim == 1 and np.isfinite(r.result).all(),
                 f"finite logits, request {r.rid}")
    durs = [w["dur"] for w in eng.waves()]
    warm = durs[1:]
    _require(warm, "at least two waves, to time a warm one")
    out = {"first_wave_s": durs[0], "warm_wave_s": warm,
           "compile_s": durs[0] - float(np.mean(warm)),
           "tokens_per_s_warm": (opts.max_batch * opts.seq
                                 / float(np.mean(warm)))}
    _log(f"[serve] {opts.requests} requests x {opts.seq} tokens, batch "
         f"{opts.max_batch}: first wave {durs[0]:.3f} s (compile + run), "
         "warm waves " + ", ".join(f"{d:.4f}" for d in warm) + " s")
    _log(f"[serve] cold compile ~{out['compile_s']:.3f} s (first wave - "
         f"mean warm wave) | warm {out['tokens_per_s_warm']:.0f} tokens/s")
    return ec, eng, reqs, done, out


def _timed(fn, *args):
    """(result, first-call seconds, warm-call seconds)."""
    t0 = time.perf_counter()
    _ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = _ready(fn(*args))
    return res, first, time.perf_counter() - t0


def one_chip(cfg, *, seq: int = 16384, num_chunks: int = 8,
             requests: int = 4, max_batch: int = 1, ref_seq: int = 2048,
             ref_chunks: int = 8) -> dict:
    """Serve ``requests`` prompts through the engine ``serve`` builds, then
    check the served logits (see the module docstring)."""
    import jax
    from repro.core import pipeline as pp
    from repro.models.api import build_model

    opts = ServeOptions(arch=cfg.arch, preset="full", executor="jax",
                        requests=requests, seq=seq, num_chunks=num_chunks,
                        max_batch=max_batch, attn_backend="pallas",
                        pool_backend="paged")
    ec, eng, reqs, done, out = _serve(cfg, opts)
    _require(ec.num_stages == 1 and ec.tp == 1, (ec.num_stages, ec.tp))
    staged, topo = eng.executor.staged, eng.executor.topo
    stats = jax.devices()[0].memory_stats()
    out["peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
    _log(f"[memory] peak_bytes_in_use {out['peak_bytes_in_use']} "
         f"({out['peak_bytes_in_use'] / 2**30:.3f} GiB)")

    def pipeline(backend: str, m: int, s: int):
        run = dc_replace(eng.executor.run_cfg, num_chunks=m,
                         attn_backend=backend, pool_backend=(
                             "paged" if backend == "pallas" else "jnp"))
        plan = pp.build_plan(cfg, 1, s, run)
        _require(plan.layers_per_stage == cfg.num_layers, "one stage")
        return jax.jit(lambda st, tk: pp.prefill_pipeline(
            cfg, st, tk, plan, topo))

    # parity 1: the served logits vs the same pipeline under the jnp
    # attention reference, request 0's tokens
    toks = np.asarray(reqs[0].tokens, np.int32)[None]
    ref = pipeline("jnp", num_chunks, seq)(staged, toks)
    out["err_vs_jnp"] = _check_logits(
        "served pallas/paged vs jnp pipeline", done[0].result,
        np.asarray(ref)[0])

    # parity 2: a ref_seq-token pipeline vs the plain full-sequence forward;
    # the jnp pipeline against the same forward is the bf16 floor that the
    # kernels are held to
    toks2 = toks[:, :ref_seq]
    pipe = pipeline("pallas", ref_chunks, ref_seq)
    _assert_mosaic(pipe, staged, toks2)
    got = pipe(staged, toks2)
    floor_got = pipeline("jnp", ref_chunks, ref_seq)(staged, toks2)
    model = build_model(cfg)

    def forward_last(st, tk):
        flat = {k: v for k, v in st.items() if k != "stage_layers"}
        flat["layers"] = jax.tree.map(lambda a: a[0], st["stage_layers"])
        return model.forward(flat, tk)[:, -1]

    with jax.default_matmul_precision("highest"):
        ref2 = np.asarray(jax.jit(forward_last)(staged, toks2))[0]
    out["floor_jnp_vs_forward"] = _check_logits(
        f"{ref_seq}-token jnp pipeline vs model.forward (bf16 floor)",
        np.asarray(floor_got)[0], ref2)
    out["err_vs_forward"] = _check_logits(
        f"{ref_seq}-token pallas/paged pipeline vs model.forward",
        np.asarray(got)[0], ref2)
    _require(out["err_vs_forward"]
             <= FLOOR_FACTOR * out["floor_jnp_vs_forward"],
             ("kernels within the bf16 floor", out["err_vs_forward"],
              out["floor_jnp_vs_forward"]))
    return out


def four_chip(cfg, *, seq: int = 32768, num_chunks: int = 16,
              requests: int = 2) -> dict:
    """The served 4-stage MOCAP pipeline (MBKR on) against Terapipe (MBKR
    off) on the same tokens, plus a forced-spill MBKR run whose remote
    (qship) attention must move the bytes the wire model predicts and stay
    within the bf16 floor (see the module docstring)."""
    import jax
    from repro.core import pipeline as pp
    from repro.core import transport as tx

    opts = ServeOptions(arch=cfg.arch, preset="full", executor="jax",
                        requests=requests, seq=seq, num_chunks=num_chunks,
                        max_batch=1, attn_backend="pallas",
                        pool_backend="paged")
    ec, eng, reqs, done, out = _serve(cfg, opts)
    _require((ec.num_stages, ec.tp) == (4, 1), (ec.num_stages, ec.tp))
    staged, topo, run = (eng.executor.staged, eng.executor.topo,
                         eng.executor.run_cfg)
    mem = [d.memory_stats() for d in jax.devices()]
    out["bytes_in_use"] = [int(s["bytes_in_use"]) for s in mem]
    out["peak_bytes_in_use"] = [int(s["peak_bytes_in_use"]) for s in mem]
    _log(f"[memory] bytes_in_use per device {out['bytes_in_use']} | peak "
         f"{out['peak_bytes_in_use']}")
    # the stages spread: every chip holds a comparable share, none the
    # whole model
    _require(min(out["bytes_in_use"]) > 0.5 * max(out["bytes_in_use"]),
             f"stages spread over the chips: {out['bytes_in_use']}")

    jnp_run = dc_replace(run, attn_backend="jnp", pool_backend="jnp")
    plans = {"served": pp.build_plan(cfg, 4, seq, run)}
    for name, r in (("", run), ("_jnp", jnp_run)):
        plans["terapipe" + name] = pp.build_plan(cfg, 4, seq,
                                                 dc_replace(r, mbkr=False))
        plans["spill" + name] = pp.build_plan(cfg, 4, seq, dc_replace(
            r, mbkr_spill_chunks=SPILL_CHUNKS))
    _require(plans["served"].mode == "mocap", "MBKR on in the served plan")
    _require(plans["terapipe"].p2 == num_chunks, "Terapipe plan")
    # chunks >= p2 spill; a chunk after p2 then reads a spilled one remotely
    _require(plans["spill"].p2 <= num_chunks - 2, "a spilled chunk is read")
    for name, p in plans.items():
        _log(f"[4-chip] {name}: {cfg.num_layers} layers, "
             f"{p.layers_per_stage} per stage, p2={p.p2} slots "
             f"{p.num_slots}")
    toks = np.asarray(reqs[0].tokens, np.int32)[None]
    logits = {}
    for name in ("terapipe", "terapipe_jnp", "spill_jnp"):
        res, first, wall = _timed(jax.jit(
            lambda st, tk, p=plans[name]: pp.prefill_pipeline(
                cfg, st, tk, p, topo)), staged, toks)
        logits[name] = np.asarray(res)[0]
        out[name] = {"first_s": first, "wall_s": wall}
        _log(f"[4-chip] {name}: first call {first:.3f} s (compile + run), "
             f"warm call {wall:.4f} s")
    (spill, led), first, wall = _timed(jax.jit(
        lambda st, tk: pp.prefill_pipeline(cfg, st, tk, plans["spill"], topo,
                                           return_ledger=True)),
        staged, toks)
    logits["spill"] = np.asarray(spill)[0]
    led = tx.ledger_to_dict(led)
    expect = tx.analytic_wire_bytes(plans["spill"], cfg, 1)
    _log(f"[4-chip] spill {SPILL_CHUNKS}: first call {first:.3f} s (compile "
         f"+ run), warm call {wall:.4f} s (with the ledger) | qship_q "
         f"{led['qship_q']:.0f} B (model {expect['qship_q']:.0f}), "
         f"qship_state {led['qship_state']:.0f} B (model "
         f"{expect['qship_state']:.0f})")
    out["spill"] = {"first_s": first, "wall_s": wall, "ledger": led}
    for key in ("qship_q", "qship_state", "spill"):
        _require(expect[key] > 0 and abs(led[key] - expect[key])
                 < 0.01 * expect[key], ("remote attention ran", key,
                                        led[key], expect[key]))
    out["err_served_vs_terapipe"] = _check_logits(
        f"served MBKR (p2={plans['served'].p2}) vs Terapipe",
        done[0].result, logits["terapipe"])
    # the bf16 floor at full depth: the same Terapipe pipeline under the
    # Pallas kernels and under the jnp reference
    floor = out["floor_pallas_vs_jnp"] = rel_err(logits["terapipe"],
                                                 logits["terapipe_jnp"])
    _log(f"[parity] {cfg.num_layers}-layer Terapipe pallas vs jnp (bf16 "
         f"floor): rel L2 err {floor:.3e} (limit {DEEP_LOGITS_RTOL:.1e})")
    # the remote (qship) path is held to that floor: the spill run against
    # Terapipe (only the order of the softmax combine and the bf16 wire of
    # the remote partials differ), with and without the kernels, and the
    # Pallas spill run against the jnp one (the kernels on the remote path)
    checks = [(np.isfinite(floor) and floor < DEEP_LOGITS_RTOL,
               ("bf16 floor", floor))]
    for key, (a, b, what) in {
            "err_spill_vs_terapipe": (
                "spill", "terapipe",
                f"MBKR spill {SPILL_CHUNKS} (p2={plans['spill'].p2}, "
                "qship) vs Terapipe"),
            "err_spill_jnp_vs_terapipe_jnp": (
                "spill_jnp", "terapipe_jnp",
                f"jnp MBKR spill {SPILL_CHUNKS} vs jnp Terapipe"),
            "err_spill_vs_spill_jnp": (
                "spill", "spill_jnp",
                f"MBKR spill {SPILL_CHUNKS} pallas vs jnp")}.items():
        out[key] = err = rel_err(logits[a], logits[b])
        _log(f"[parity] {what}: rel L2 err {err:.3e} (limit "
             f"{FLOOR_FACTOR:g} x floor = {FLOOR_FACTOR * floor:.3e})")
        checks.append((np.isfinite(err) and err <= FLOOR_FACTOR * floor,
                       (what, err, floor)))
    # every reading is printed before the first failed check ends the run
    for ok, what in checks:
        _require(ok, what)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip (default); "
                         "4: the four-stage MBKR pipeline vs Terapipe")
    args = ap.parse_args(argv)

    devices = serve.jax_devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 2
    _log(f"[device] {dev.platform} {dev.device_kind} x {len(devices)}")
    full = get_config("qwen3-8b")
    if args.chips == 1:
        cfg = replace(full, num_layers=8)
        _log(f"[config] {cfg.arch} d_model {cfg.d_model} heads "
             f"{cfg.num_heads}/{cfg.num_kv_heads} head_dim "
             f"{cfg.resolved_head_dim} d_ff {cfg.d_ff} vocab "
             f"{cfg.vocab_size} {cfg.dtype} | layers cut "
             f"{full.num_layers} -> {cfg.num_layers}")
        result = one_chip(cfg)
    else:
        cfg = full
        _log(f"[config] {cfg.arch} full depth {cfg.num_layers} layers, "
             f"{cfg.dtype}")
        result = four_chip(cfg)
    _log("[result] " + json.dumps(result))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
