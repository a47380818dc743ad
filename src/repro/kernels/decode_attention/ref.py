"""Oracles: models/attention holds the pure-jnp references.

``decode_attention_ref`` accepts scalar or per-slot [B] lengths;
``paged_decode_attention_ref`` is the block-table variant (gather, then
``decode_attention``), the path every platform but the TPU serves.
"""

from repro.models.attention import (  # noqa: F401
    decode_attention as decode_attention_ref,
    paged_decode_attention_jnp as paged_decode_attention_ref,
)
