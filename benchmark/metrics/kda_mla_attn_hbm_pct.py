"""kda_mla_attn_hbm_pct — share of the chip's HBM bandwidth the latent layers' paged attention reaches (Kimi Linear's keys).

Needed bytes of the two latent layers' attention in one decode step — the
latent row and the shared key of every attended token at PUBLISHED widths,
(512 + 64) x 2 B = 1,152 B a token a layer (2,304 B over both; the pools hold
64 lanes of zeros more behind the key: the implementation's), read once for
all 32 heads, plus the absorbed queries in and the latent outputs back — over
819 GB/s (``peaks.json``) and over the ``mla_paged_attention`` kernel's
device time in a ``_decode`` execution (``mla_attn_ms_per_step``):
``lib/costs_kda_mla_moe.latent_attention_share``. The accepted
``mla_attn_hbm_pct`` counts every layer of the file as a latent one.
"""

from benchmark.lib.costs_kda_mla_moe import latent_attention_share


def read(view):
    return latent_attention_share(view)
