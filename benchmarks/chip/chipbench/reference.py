"""The plain reference: the same semantics as the program's device path,
written again from the paper's equations and the configuration's stated
sizes, importing nothing of the program.

* ``pixel_cascade``: Eqs. 1-6 (frame differencing, grayscale threshold,
  3x3 dilation then erosion) and the foreground count, in int64 NumPy.
* ``detections``: 8-connected components (``scipy.ndimage.label``), the
  size and aspect filter of the paper's Sec. IV-C, and the centred crop
  of the middle frame.
* ``crop_tokens``: the patch tokenizer (an LSH codebook: fixed random
  projection and sign hash), copied from the program's
  ``synthetic_video.crops_to_tokens``.
* ``classifier``: the CQ classifier's forward pass from its stated sizes
  (RMSNorm, causal multi-head attention with NeoX rotary embeddings, a
  gated SiLU MLP, mean pooling, a linear head, softmax), in float64, or
  with every weight and activation rounded to bfloat16 for the control.
* ``triage``: Eqs. 8-9's three-way split and the per-row escalation
  buffer (stable compaction, capped at the capacity).
* ``threshold_scan``: the superstep's per-tick Eqs. 8-9 update of every
  (query, edge) row, in float64.
* ``expected_decisions``: what the event engine must answer for each item,
  from its route and where it finished: accept is true, reject false, an
  escalation or any item answered away from the edge that triaged it
  takes the accurate model's answer (the ground truth), an escalation past
  the buffer the edge's own ``conf > 0.5``.
* ``f_score``: F_lambda from decisions and ground truth.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

Box = Tuple[int, int, int, int, int]          # y0, x0, y1, x1, area


# --- pixel path -----------------------------------------------------------------

def pixel_cascade(f0, f1, f2, threshold: int, maxval: int = 255
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """(B, H, W, 3) frames -> (mask (B, H, W) int32, counts (B,) int32)."""
    f0, f1, f2 = (np.asarray(f, np.int64) for f in (f0, f1, f2))
    da = np.bitwise_and(np.abs(f1 - f0), np.abs(f2 - f1))
    gray = (da[..., 0] * 299 + da[..., 1] * 587 + da[..., 2] * 114) // 1000
    m = np.where(gray > threshold, maxval, 0)
    B, H, W = m.shape

    def morph(x, red, fill):
        xp = np.pad(x, ((0, 0), (1, 1), (1, 1)), constant_values=fill)
        acc = xp[:, 0:H, 0:W]
        for dy in range(3):
            for dx in range(3):
                acc = red(acc, xp[:, dy:dy + H, dx:dx + W])
        return acc

    m = morph(morph(m, np.maximum, 0), np.minimum, maxval)
    return m.astype(np.int32), (m > 0).sum(axis=(1, 2)).astype(np.int32)


def boxes(mask: np.ndarray, min_area: int, max_aspect: float = 6.0
          ) -> List[Box]:
    """One camera's filtered components, in raster order of their first
    pixel."""
    from scipy import ndimage
    lab, n = ndimage.label(mask > 0, structure=np.ones((3, 3), bool))
    out = []
    for i, sl in enumerate(ndimage.find_objects(lab), start=1):
        area = int((lab[sl] == i).sum())
        y0, y1 = sl[0].start, sl[0].stop - 1
        x0, x1 = sl[1].start, sl[1].stop - 1
        h, w = y1 - y0 + 1, x1 - x0 + 1
        if area < min_area or max(h, w) / max(min(h, w), 1) > max_aspect:
            continue
        out.append((y0, x0, y1, x1, area))
    return out


def crop(frame: np.ndarray, box: Box, side: int) -> np.ndarray:
    """The ``side`` x ``side`` patch of ``frame`` centred on ``box``,
    shifted inside the frame."""
    H, W = frame.shape[:2]
    cy, cx = (box[0] + box[2]) // 2, (box[1] + box[3]) // 2
    y0 = int(np.clip(cy - side // 2, 0, H - side))
    x0 = int(np.clip(cx - side // 2, 0, W - side))
    return frame[y0:y0 + side, x0:x0 + side]


def crop_tokens(crops: np.ndarray, vocab_size: int, patch: int = 8,
                seed: int = 7) -> np.ndarray:
    """(N, S, S, 3) uint8 crops -> (N, (S/patch)^2) int32 patch tokens."""
    N, S = crops.shape[0], crops.shape[1]
    t = S // patch
    x = crops.reshape(N, t, patch, t, patch, 3).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(N, t * t, patch * patch * 3).astype(np.float64)
    x = (x - x.mean(-1, keepdims=True)) / (x.std(-1, keepdims=True) + 1e-6)
    rng = np.random.default_rng(seed)
    nbits = max(int(np.floor(np.log2(max(vocab_size - 1, 2)))), 1)
    proj = rng.normal(size=(patch * patch * 3, nbits))
    tokens = ((x @ proj) > 0) @ (1 << np.arange(nbits))
    return np.minimum(tokens, vocab_size - 1).astype(np.int32)


# --- CQ classifier --------------------------------------------------------------

def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


def classifier(spec: Dict, weights: Dict, tokens: np.ndarray,
               control: bool = False) -> np.ndarray:
    """P(query class) for each row of ``tokens`` (N, T).

    ``spec`` is the configuration's ``classifier`` block; ``weights`` the
    benchmark's weights as NumPy arrays, keyed as the program keys them.
    The reference computes in float64; ``control=True`` rounds every
    weight and every intermediate to bfloat16 (matmuls accumulate in
    float32, as the MXU does)."""
    if control:
        dt, r = np.float32, _bf16
    else:
        dt, r = np.float64, (lambda a: a)
    w = {k: r(np.asarray(v, dt)) for k, v in _flat(weights).items()}
    eps = float(spec["norm_eps"])
    H, hd = int(spec["num_heads"]), int(spec["head_dim"])
    L = int(spec["num_layers"])

    def norm(x, scale):
        ms = np.mean(np.square(x), axis=-1, keepdims=True)
        return r(x / np.sqrt(ms + eps) * scale)

    N, T = tokens.shape
    x = r(w["embed"][tokens])                                   # (N, T, D)
    pos = np.arange(T, dtype=dt)
    inv = 1.0 / (float(spec["rope_theta"])
                 ** (np.arange(0, hd, 2, dtype=dt) / hd))
    ang = pos[:, None] * inv[None, :]                          # (T, hd/2)
    cos, sin = np.cos(ang)[None, :, None, :], np.sin(ang)[None, :, None, :]

    def rope(q):
        h = hd // 2
        q1, q2 = q[..., :h], q[..., h:]
        return r(np.concatenate([q1 * cos - q2 * sin, q2 * cos + q1 * sin],
                                axis=-1))

    causal = np.tril(np.ones((T, T), bool))
    for i in range(L):
        h = norm(x, w["layers.norm1.scale"][i])
        q = rope(r(np.einsum("ntd,dhk->nthk", h, w["layers.attn.wq"][i])))
        k = rope(r(np.einsum("ntd,dhk->nthk", h, w["layers.attn.wk"][i])))
        v = r(np.einsum("ntd,dhk->nthk", h, w["layers.attn.wv"][i]))
        s = np.einsum("nqhk,nshk->nhqs", q, k) / np.sqrt(hd)
        s = np.where(causal[None, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = r(p / p.sum(-1, keepdims=True))
        o = r(np.einsum("nhqs,nshk->nqhk", p, v))
        x = r(x + np.einsum("nthk,hkd->ntd", o, w["layers.attn.wo"][i]))
        h2 = norm(x, w["layers.norm2.scale"][i])
        g = r(h2 @ w["layers.mlp.wg"][i])
        u = r(h2 @ w["layers.mlp.wi"][i])
        act = r(g / (1.0 + np.exp(-g)) * u)
        x = r(x + act @ w["layers.mlp.wo"][i])
    x = norm(x, w["final_norm.scale"])
    logits = np.mean(x, axis=1) @ w["cls_head.w"] + w["cls_head.b"]
    z = logits - logits.max(-1, keepdims=True)
    p = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    return p[:, int(spec["query_class_index"])]


def _flat(tree: Dict, prefix: str = "") -> Dict[str, np.ndarray]:
    out = {}
    for k, v in tree.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flat(v, key + "."))
        else:
            out[key] = np.asarray(v)
    return out


# --- fleet triage and supersteps -----------------------------------------------

def triage(conf: np.ndarray, thresholds: np.ndarray, capacity: int
           ) -> Tuple[np.ndarray, np.ndarray]:
    """Rows of confidences (..., N) with their (..., 2) [alpha, beta] ->
    routes (0 accept, 1 reject, 2 escalate) and escalation slots (-1 when
    none or past ``capacity``)."""
    conf = np.asarray(conf, np.float32)
    th = np.asarray(thresholds, np.float32)
    routes = np.where(conf > th[..., 0:1], 0,
                      np.where(conf < th[..., 1:2], 1, 2)).astype(np.int32)
    esc = routes == 2
    pos = np.cumsum(esc, axis=-1) - 1
    slots = np.where(esc & (pos < capacity), pos, -1).astype(np.int32)
    return routes, slots


def threshold_scan(th0: np.ndarray, mask: np.ndarray, drain: np.ndarray,
                   gains: np.ndarray, control: bool = False) -> np.ndarray:
    """Eqs. 8-9 per (query, edge) row for each tick of a superstep.

    ``th0`` (R, 2) starting [alpha, beta]; ``mask`` (S, R) rows that had
    items each tick; ``drain`` (R,) the row's held queue drain; ``gains``
    [gamma1, gamma1_up, gamma2, interval_s].  A row with items takes
    alpha <- clip(alpha - g (drain - interval), 0.5, 1), where g is gamma1
    when the drain is at least one interval and gamma1_up otherwise, and
    beta <- gamma2 (1 - alpha); a row without items holds.  Returns the
    (S, R, 2) thresholds after each tick.  float64, or bfloat16 at every
    step for the control."""
    r = _bf16 if control else (lambda a: a)
    dt = np.float32 if control else np.float64
    g1, g1u, g2, interval = (dt(v) for v in np.asarray(gains, np.float64))
    drain = r(np.asarray(drain, dt))
    th = r(np.asarray(th0, dt))
    gain = np.where(drain >= interval, g1, g1u)
    out = np.empty((mask.shape[0],) + th.shape, dt)
    for s in range(mask.shape[0]):
        alpha = r(np.clip(r(th[:, 0] - r(gain * r(drain - interval))),
                          0.5, 1.0))
        new = np.stack([alpha, r(g2 * r(1.0 - alpha))], axis=-1)
        th = np.where(np.asarray(mask[s], bool)[:, None], new, th)
        out[s] = th
    return out


# --- event engine ----------------------------------------------------------------

ACCEPT, REJECT, ESCALATE = 0, 1, 2
CLOUD = 0


def expected_decisions(route: np.ndarray, slot: np.ndarray,
                       conf: np.ndarray, truth: np.ndarray,
                       home: np.ndarray, node: np.ndarray) -> np.ndarray:
    """The answer each finished item must carry.

    ``route`` and ``slot`` are the reference's triage of the item on the
    edge ``home`` that triaged it (route -1 where no triage ran: the item
    was shed, failed over, or its query was not live there), ``conf`` the
    float32 confidence it was routed on, ``node`` where it finished
    (0 is the cloud).

    * escalated into the buffer: re-classified by the accurate model,
      wherever Eq. 7 sent it, so the answer is the ground truth;
    * answered on another node than ``home``: shed or failed over to the
      accurate model, the ground truth;
    * accept: true; reject: false;
    * escalated past the buffer: the edge keeps it, ``conf > 0.5``;
    * never triaged and answered on its own edge: the edge's prior,
      ``conf > 0.5`` (a straggler of a retired query).
    """
    route, slot = np.asarray(route), np.asarray(slot)
    truth = np.asarray(truth, bool)
    away = np.asarray(node) != np.asarray(home)
    prior = np.asarray(conf) > 0.5
    out = np.where(route == ACCEPT, True,
                   np.where(route == REJECT, False, prior))
    out = np.where((route == ESCALATE) & (slot >= 0), truth, out)
    return np.where(away, truth, out).astype(bool)


def f_score(decisions: np.ndarray, truths: np.ndarray,
            lam: float = 2.0) -> float:
    """F_lambda of boolean decisions against boolean ground truth."""
    d, y = np.asarray(decisions, bool), np.asarray(truths, bool)
    tp = int(np.count_nonzero(d & y))
    fp = int(np.count_nonzero(d & ~y))
    fn = int(np.count_nonzero(~d & y))
    p = tp / max(tp + fp, 1)
    r = tp / max(tp + fn, 1)
    if p + r == 0:
        return 0.0
    return (1 + lam ** 2) * p * r / (lam ** 2 * p + r)
