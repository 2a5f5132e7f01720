"""Bytes the paged pool takes for ONE cached position over all layers,
sidecars included: the pool's own ``block_bytes()`` over its
``block_size``, as the kind printed it in its ``window``
(``kv_pool_bytes_per_token``).  A latent record's published entry is
``(kv_lora_rank + qk_rope_head_dim)`` numbers a layer; what the layout
pads it to shows here."""


def read(run):
    return run.get("kv_pool_bytes_per_token")
