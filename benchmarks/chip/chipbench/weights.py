"""The CQ classifier's weights, made on the device from the seed in one
jitted call, keyed as the program keys them, from the configuration's
stated sizes."""
from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

#: leaf -> (shape, kind, fan-in); kind "w" ~ N(0, 1/fan_in), "scale" ~
#: 1 + N(0, 0.1^2), "bias" ~ N(0, 0.1^2)
Layout = Dict[str, Tuple[Tuple[int, ...], str, int]]


def layout(spec: Dict) -> Layout:
    L, D, H = spec["num_layers"], spec["d_model"], spec["num_heads"]
    KV, hd, F = spec["num_kv_heads"], spec["head_dim"], spec["d_ff"]
    V, C = spec["vocab_size"], spec["num_query_classes"]
    return {
        "embed": ((V, D), "w", D),
        "layers.attn.wq": ((L, D, H, hd), "w", D),
        "layers.attn.wk": ((L, D, KV, hd), "w", D),
        "layers.attn.wv": ((L, D, KV, hd), "w", D),
        "layers.attn.wo": ((L, H, hd, D), "w", H * hd),
        "layers.mlp.wi": ((L, D, F), "w", D),
        "layers.mlp.wg": ((L, D, F), "w", D),
        "layers.mlp.wo": ((L, F, D), "w", F),
        "layers.norm1.scale": ((L, D), "scale", 0),
        "layers.norm2.scale": ((L, D), "scale", 0),
        "final_norm.scale": ((D,), "scale", 0),
        "cls_head.w": ((D, C), "w", D),
        "cls_head.b": ((C,), "bias", 0),
        "lm_head": ((D, V), "w", D),
    }


def _nest(flat: Dict) -> Dict:
    out: Dict = {}
    for key, v in flat.items():
        node = out
        *head, last = key.split(".")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def make(spec: Dict, seed: int):
    """The weights on the default device, in the configuration's dtype."""
    import jax
    import jax.numpy as jnp
    lay = layout(spec)
    dtype = jnp.dtype(spec["dtype"])

    def build(key):
        keys = jax.random.split(key, len(lay))
        flat = {}
        for k, (name, (shape, kind, fan)) in zip(keys, lay.items()):
            z = jax.random.normal(k, shape, jnp.float32)
            if kind == "scale":
                v = 1.0 + 0.1 * z
            elif kind == "bias":
                v = 0.1 * z
            else:
                v = z / math.sqrt(fan)
            flat[name] = v.astype(dtype)
        return _nest(flat)

    s = int(np.random.SeedSequence(seed % (1 << 64)).generate_state(1)[0])
    return jax.jit(build)(jax.random.key(s))
