"""A configuration file as numbers, and seeded weights for it.

The configuration file (configs/<config>.json) states the model as it is
run under "model".  The weights are made here, on the device, in one
jitted call from the run's seed, in bfloat16 (the type they are served
in), laid out as the serving program's parameter tree expects them:

  embedding.embed [Vp, E], embedding.unemb [E, Vp]   (Vp: vocab padded to
                                                      a multiple of 256)
  final_norm.scale [E]
  segments[0]: ln1.scale, ln2.scale [L, E]; attn.wq [L, E, H*hd],
               attn.wk / attn.wv [L, E, KV*hd], attn.wo [L, H*hd, E];
               mlp.wg / mlp.wu [L, E, F], mlp.w2 [L, F, E]

Matrices are N(0, std^2); norm scales are uniform on the file's
"norm_scale" range, so a program that dropped a scale would be seen.  The
plain reference (reference.py) reads the same arrays; both sides get the
weights from here and neither from the other.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np


@dataclasses.dataclass(frozen=True)
class Model:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rope_fraction: float
    norm_eps: float

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256

    @property
    def rot_dims(self) -> int:
        """Head dims that RoPE rotates, in interleaved pairs."""
        return int(self.head_dim * self.rope_fraction) // 2 * 2


def load_config(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def model_of(config: dict) -> Model:
    m = config["model"]
    return Model(**{f.name: m[f.name] for f in dataclasses.fields(Model)})


def program_overrides(model: Model) -> dict:
    """The program's ModelConfig fields that the file states."""
    return {"n_layers": model.n_layers, "d_model": model.d_model,
            "n_heads": model.n_heads, "n_kv_heads": model.n_kv_heads,
            "head_dim": model.head_dim, "d_ff": model.d_ff,
            "vocab": model.vocab, "rope_theta": model.rope_theta,
            "rope_fraction": model.rope_fraction,
            "schedule": (("attn", model.n_layers),)}


def shapes(m: Model) -> dict:
    E, H, KV, hd, F, L = (m.d_model, m.n_heads, m.n_kv_heads, m.head_dim,
                          m.d_ff, m.n_layers)
    return {
        "embedding": {"embed": (m.padded_vocab, E),
                      "unemb": (E, m.padded_vocab)},
        "final_norm": {"scale": (E,)},
        "segments": ({
            "ln1": {"scale": (L, E)},
            "attn": {"wq": (L, E, H * hd), "wk": (L, E, KV * hd),
                     "wv": (L, E, KV * hd), "wo": (L, H * hd, E)},
            "ln2": {"scale": (L, E)},
            "mlp": {"wg": (L, E, F), "wu": (L, E, F), "w2": (L, F, E)},
        },),
    }


def seed_words(seed: int) -> tuple:
    """Two 32-bit words from a seed of any size."""
    w = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(w[0]), int(w[1])


def make_weights(m: Model, seed: int, init: dict, out_shardings=None):
    """Seeded bf16 weights on the device, made by one jitted call."""
    import jax
    import jax.numpy as jnp
    std = float(init["std"])
    lo, hi = (float(v) for v in init["norm_scale"])
    tree = shapes(m)
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(d, int) for d in x))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple)
                 and all(isinstance(d, int) for d in x))[0]]

    def build(key):
        out = []
        for i, (shape, path) in enumerate(zip(leaves, paths)):
            k = jax.random.fold_in(key, i)
            if path.endswith("['scale']"):
                x = jax.random.uniform(k, shape, jnp.float32, lo, hi)
            else:
                x = jax.random.normal(k, shape, jnp.float32) * std
            out.append(x.astype(jnp.bfloat16))
        return jax.tree.unflatten(treedef, out)

    w0, w1 = seed_words(seed)
    key = jax.random.fold_in(jax.random.key(w0), w1)
    fn = jax.jit(build, out_shardings=out_shardings)
    return jax.block_until_ready(fn(key))
