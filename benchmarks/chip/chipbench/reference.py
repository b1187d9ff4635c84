"""The plain reference: the decoder's forward pass in float32 jax.numpy.

No kernels, no cache, no batching, and nothing of the serving program: it
follows the configuration file's "model" numbers and reads the weights
that model.make_weights made.  Every matrix product runs at
Precision.HIGHEST, since a float32 product on a TPU is otherwise computed
in bfloat16 passes.

The layer (pre-norm, as the published models are):

  h = rmsnorm(x) * ln1;  q, k, v = h Wq, h Wk, h Wv
  RoPE on the first rot_dims of each head, in interleaved pairs
  (x[2i], x[2i+1]) at angle pos / theta^(2i / rot_dims)
  causal softmax(q k^T / sqrt(head_dim)) v, each KV head shared by
  n_heads / n_kv_heads query heads
  x = x + o Wo;  h = rmsnorm(x) * ln2;  x = x + (silu(h Wg) * h Wu) W2

then rmsnorm(x) * final_norm and the logits h Unemb over the first
`vocab` columns.  The whole stack is one scan over the layers; queries are
taken in blocks so that the score matrix of a long sequence fits.
"""
from __future__ import annotations

import functools

import numpy as np

QBLOCK = 256        # query rows per attention block


def padded(n: int, base: int = 512) -> int:
    """The rung of {base, 1.5 base} x 2^k at or above n: sequence lengths
    and row counts are padded to a few rungs, so that a handful of
    compiled programs serve every run."""
    while True:
        for cand in (base, base + base // 2):
            if cand >= n:
                return cand
        base *= 2


def _mm(a, b, spec):
    import jax
    import jax.numpy as jnp
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, pos, m):
    import jax.numpy as jnp
    rot = m.rot_dims
    if rot == 0 or m.rope_theta <= 0:
        return x
    inv = 1.0 / (m.rope_theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                                  / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    rotated = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(x[..., :rot].shape)
    return jnp.concatenate([rotated, x[..., rot:]], axis=-1)


def _attention(q, k, v, m):
    """q [S, H, hd], k/v [S, KV, hd] -> [S, H*hd], causal, in query
    blocks of QBLOCK rows."""
    import jax
    import jax.numpy as jnp
    S = q.shape[0]
    G = m.n_heads // m.n_kv_heads
    qb = min(QBLOCK, S)
    nb = S // qb
    qs = q.reshape(nb, qb, m.n_kv_heads, G, m.head_dim)
    cols = jnp.arange(S)

    def block(args):
        i, qblk = args
        rows = i * qb + jnp.arange(qb)
        s = _mm(qblk, k, "qkgd,skd->kgqs") / np.sqrt(m.head_dim)
        s = jnp.where((cols[None, :] <= rows[:, None])[None, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm(p, v, "kgqs,skd->qkgd")

    o = jax.lax.map(block, (jnp.arange(nb), qs))
    return o.reshape(S, m.n_heads * m.head_dim)


def int8_weight(w):
    """A weight matrix [..., K, N] through symmetric int8 with one scale
    per output column (max |w| over K / 127), back in float32: the
    control's precision, one step below bfloat16."""
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-30) / 127
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _layer(x, w, pos, m, quant):
    import jax
    import jax.numpy as jnp
    f32 = int8_weight if quant else (lambda a: a.astype(jnp.float32))
    S = x.shape[0]
    h = _rms(x, w["ln1"]["scale"], m.norm_eps)
    a = w["attn"]
    q = _mm(h, f32(a["wq"]), "se,ef->sf").reshape(S, m.n_heads, m.head_dim)
    k = _mm(h, f32(a["wk"]), "se,ef->sf").reshape(S, m.n_kv_heads,
                                                  m.head_dim)
    v = _mm(h, f32(a["wv"]), "se,ef->sf").reshape(S, m.n_kv_heads,
                                                  m.head_dim)
    q, k = _rope(q, pos, m), _rope(k, pos, m)
    x = x + _mm(_attention(q, k, v, m), f32(a["wo"]), "sf,fe->se")
    h = _rms(x, w["ln2"]["scale"], m.norm_eps)
    f = w["mlp"]
    gate = _mm(h, f32(f["wg"]), "se,ef->sf")
    up = _mm(h, f32(f["wu"]), "se,ef->sf")
    return x + _mm(jax.nn.silu(gate) * up, f32(f["w2"]), "sf,fe->se")


@functools.lru_cache(maxsize=None)
def _forward_fn(m, quant: bool):
    import jax
    import jax.numpy as jnp

    def fwd(weights, tokens, rows):
        x = jnp.take(weights["embedding"]["embed"], tokens,
                     axis=0).astype(jnp.float32)
        pos = jnp.arange(tokens.shape[0])

        def body(x, w):
            return _layer(x, w, pos, m, quant), None

        x, _ = jax.lax.scan(body, x, weights["segments"][0])
        h = _rms(jnp.take(x, rows, axis=0), weights["final_norm"]["scale"],
                 m.norm_eps)
        unemb = weights["embedding"]["unemb"][:, :m.vocab]
        unemb = int8_weight(unemb) if quant else unemb.astype(jnp.float32)
        return _mm(h, unemb, "re,ev->rv")

    return jax.jit(fwd)


def logits(weights, m, tokens: np.ndarray, rows: np.ndarray,
           quant: bool = False) -> np.ndarray:
    """float32 logits [len(rows), vocab] at positions `rows` of `tokens`
    (the logits at position p score the token at p + 1); `quant` runs the
    control, every weight matrix through int8 (int8_weight)."""
    import jax.numpy as jnp
    S = len(tokens)
    toks = np.zeros(padded(S), np.int32)   # pads follow: causality hides them
    toks[:S] = tokens
    nr = len(rows)
    rp = np.zeros(padded(nr, 256), np.int32)
    rp[:nr] = rows
    out = _forward_fn(m, quant)(weights, jnp.asarray(toks),
                                jnp.asarray(rp))
    return np.asarray(out)[:nr]


def served_gaps(weights, m, prompt: np.ndarray, served: np.ndarray,
                control: bool = False) -> dict:
    """The reference's reading of tokens `served` after `prompt`.

    gap[i]: the reference's best logit at served token i's position minus
    its logit for that token (0 where it is the reference's argmax).
    With `control`, also control_gap[i]: the same gap for the token that
    the int8 control puts first at that position (teacher-forced on the
    served tokens, so it needs no decoding of its own)."""
    served = np.asarray(served, np.int64)
    n = len(served)
    if served.min() < 0 or served.max() >= m.vocab:
        return {"gap": np.full(n, np.inf), "hit": np.zeros(n, bool)}
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          served.astype(np.int32)])[:-1]
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    z = logits(weights, m, seq, rows)
    best = z.max(axis=1)
    out = {"gap": best - z[np.arange(n), served],
           "hit": z.argmax(axis=1) == served}
    if control:
        c = logits(weights, m, seq, rows, quant=True).argmax(axis=1)
        out["control_gap"] = best - z[np.arange(n), c]
    return out
