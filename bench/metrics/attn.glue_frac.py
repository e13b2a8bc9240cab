"""Share of the attention scopes' device time (``layer.attn_self`` and
``layer.attn_pool``) spent outside the two kernels' custom calls
(``chunk_attention``, ``pool_attention_paged``): the layout glue around
them (transposes, pads, state combines)."""
import scopes


def read(run):
    return scopes.attn_glue_frac(run)
