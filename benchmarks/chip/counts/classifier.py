"""Forward FLOPs of the CQ classifier for ``n`` crops of ``t`` tokens,
from the configuration's stated sizes (multiply-adds count two)."""


def forward_flops(spec, n: int, t: int) -> float:
    D, H, KV = spec["d_model"], spec["num_heads"], spec["num_kv_heads"]
    hd, F, L = spec["head_dim"], spec["d_ff"], spec["num_layers"]
    C = spec["num_query_classes"]
    per_token = (2 * D * (H + 2 * KV) * hd      # q, k, v projections
                 + 2 * H * hd * D               # output projection
                 + 2 * 2 * t * H * hd           # scores and weighted sum
                 + 2 * 3 * D * F)               # gated MLP
    return float(n * (L * t * per_token + 2 * D * C))
