"""Assert the tier-1 SKIP matrix: the only expected skips are hypothesis.

This script parses a ``pytest -rs`` log and asserts the exact expected skip
counts per reason class:

- hypothesis skips: exactly 0 when hypothesis is importable (CI installs
  it), exactly 5 otherwise (4 importorskip modules — including the prefix
  radix property tests — + the guarded ragged-occupancy property test).
- anything else: unknown skip reason -> fail. Notably the TPU compile tests
  (tests/test_tpu_compile.py) must compile, not skip, wherever the TPU
  compiler is installed, and the paged pool kernel's manual-DMA path runs
  under interpret mode on the CPU.

Usage:
  PYTHONPATH=src python -m pytest -q -rs 2>&1 | tee pytest-report.log
  PYTHONPATH=src python tests/check_skips.py pytest-report.log
"""
from __future__ import annotations

import re
import sys

SKIP_RE = re.compile(r"^SKIPPED \[(\d+)\] [^:]+(?::\d+)?: (.*)$", re.M)

# the whisper-encoder case inside a hypothesis property test
_ALLOWED_CONDITIONAL = ("causal-only",)


def main(path: str) -> int:
    try:
        import hypothesis  # noqa: F401
        have_hyp = True
    except ImportError:
        have_hyp = False

    text = open(path).read()
    skips = [(int(m.group(1)), m.group(2).strip())
             for m in SKIP_RE.finditer(text)]
    n_hyp = sum(c for c, r in skips if "hypothesis" in r)
    unknown = [(c, r) for c, r in skips
               if "hypothesis" not in r
               and not any(a in r for a in _ALLOWED_CONDITIONAL)]

    exp_hyp = 0 if have_hyp else 5
    ok = True
    if n_hyp != exp_hyp:
        ok = False
        print(f"FAIL: {n_hyp} hypothesis skips, expected {exp_hyp} "
              f"(hypothesis importable={have_hyp})")
    if unknown:
        ok = False
        print(f"FAIL: unknown skip reasons: {unknown}")
    if ok:
        print(f"skip matrix OK: hypothesis={n_hyp}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
