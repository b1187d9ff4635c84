"""Per-request sampling parameters for the serving engine.

`SamplingParams` is the host-side request option; the jit-side math lives in
core/embedding.sample_token (Gumbel-max over the tp-sharded vocab) and is
threaded through launch/steps' prefill/decode bundles as a per-slot "lane":
a dict of [B] arrays (temperature / top_k / seed) the engine scatters into
whenever a request is admitted to a slot.  Sampling therefore happens inside
the jitted step — no logits ever leave the device, and one compiled decode
step serves any mix of greedy and sampled requests.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.core.embedding import TOP_K_CAP


def validate_sampling(params: "SamplingParams") -> None:
    """Reject unservable sampling parameters with a clear ValueError at
    construction/submit time — the jitted step would otherwise silently
    clamp them (top_k beyond the exact distributed threshold-search depth)
    or misbehave (negative temperature), deep inside the engine loop."""
    if params.temperature < 0:
        raise ValueError(
            f"temperature must be >= 0 (0 = greedy): {params.temperature}")
    if params.top_k < 0:
        raise ValueError(f"top_k must be >= 0 (0 = full vocabulary): "
                         f"{params.top_k}")
    if params.top_k > TOP_K_CAP:
        raise ValueError(
            f"top_k {params.top_k} exceeds TOP_K_CAP={TOP_K_CAP}: the "
            f"distributed top-k threshold search is exact only up to the "
            f"cap (each tp shard contributes its local top-{TOP_K_CAP}); "
            f"pass top_k <= {TOP_K_CAP}, or 0 for full-vocabulary sampling")


@dataclass(frozen=True)
class SamplingParams:
    """What a request wants from the token sampler.

    temperature  0.0 => greedy (exact argmax path inside the step);
                 > 0 => softmax(z/temperature) via Gumbel-max
    top_k        truncate to the k highest-logit tokens before sampling;
                 0 => full vocabulary (ignored when temperature == 0;
                 must be <= core.embedding.TOP_K_CAP — the distributed
                 threshold search is exact only up to the cap, and
                 out-of-range values are rejected here rather than
                 silently clamped inside the jitted step)
    seed         the request's RNG lane — (seed, position) maps to one
                 reproducible draw regardless of batching or slot placement
    """
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0

    def __post_init__(self):
        validate_sampling(self)

    @property
    def is_greedy(self) -> bool:
        return self.temperature == 0.0


GREEDY = SamplingParams()


def zero_lane(batch_size: int) -> dict:
    """Fresh per-slot lane arrays (all slots greedy) for a decode batch.

    HOST-side numpy: slot updates index by a python int, and a device
    `.at[slot].set()` would jit-compile once per distinct slot index; the
    engine converts at the step-call boundary instead."""
    return {"temperature": np.zeros((batch_size,), np.float32),
            "top_k": np.zeros((batch_size,), np.int32),
            "seed": np.zeros((batch_size,), np.int32)}


def set_lane(lane: dict, slot: int, params: SamplingParams) -> dict:
    """Scatter one request's SamplingParams into slot `slot` (functional:
    the input lane is not mutated)."""
    out = {k: v.copy() for k, v in lane.items()}
    out["temperature"][slot] = params.temperature
    out["top_k"][slot] = params.top_k
    out["seed"][slot] = params.seed
    return out


def upload(x: np.ndarray):
    """A device array of host array `x` as it is now.  `jnp.array` of a
    numpy array takes no host copy: the transfer may read `x` after the
    call returns (CPU backend, async dispatch, a busy device), so an
    in-place write that follows can reach a step already dispatched.
    The copy made here is written by nobody."""
    return jnp.asarray(x.copy())


def device_lane(lane: dict) -> dict:
    """Host lane -> device arrays for a jitted step call (`upload`)."""
    return {k: upload(v) for k, v in lane.items()}


def stack_lanes(params_list) -> dict:
    """[n] lane arrays for a row batch of SamplingParams (the schema the
    jitted steps consume; chunked-prefill rows use this directly)."""
    return {"temperature": jnp.asarray([p.temperature for p in params_list],
                                       jnp.float32),
            "top_k": jnp.asarray([p.top_k for p in params_list], jnp.int32),
            "seed": jnp.asarray([p.seed for p in params_list], jnp.int32)}


def stack_prefill_lanes(params_list, prompt_lens) -> dict:
    """[nB] lane for a batched-admission prefill: one admission group's
    SamplingParams and true prompt lengths, row-aligned with the padded
    token batch."""
    return dict(stack_lanes(params_list),
                prompt_len=jnp.asarray(list(prompt_lens), jnp.int32))
