"""How `correct` is decided, at a size a test run can hold (CPU).

A run of the harness, with the chip check skipped, serves a tiny model
through the engine and passes the check; the same run with each served
token altered where the runner produces it comes out not correct; and the
control (the reference with int8 weights) reads gaps where the served
tokens read none."""
import json
import types

import numpy as np

import _paths  # noqa: F401
import jax

import run
from chipbench import check, model, reference, registry

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab": 512}
MIX = {"loop": "open", "rate_rps": 6.0, "lead_s": 1,
       "prompt": {"dist": "pareto", "alpha": 1.2, "min": 8, "max": 24},
       "output": {"dist": "uniform", "min": 6, "max": 14},
       "sampled_share": 0.5, "sampling": {"temperature": 0.8, "top_k": 40}}
LIMIT = 0.02     # served bf16 tokens read 0 at this size (near-tie
#                  partings would read under 1e-3); altered tokens read
#                  the logit spread, about 0.1


def _run_tiny(monkeypatch, seed):
    bench = registry.load(_paths.ROOT)
    cell = registry.workload(bench, "phi4-mini-3.8b.chat")
    config = model.load_config(registry.config_file(_paths.ROOT, bench,
                                                    cell))
    config["model"].update(TINY)
    config["engine"].update(batch_size=4, kv_pool_blocks=200,
                            prefill_group_cap=2)
    config["correct"] = {"mean_logit_gap": LIMIT}
    monkeypatch.setattr(run, "MIN_TOKENS", 24)
    monkeypatch.setattr(run, "DRAIN_S", 20.0)
    args = types.SimpleNamespace(seed=seed, seconds=3.0, trace=0,
                                 control=None)
    res, _ = run.run_cell(args, bench, cell, config, dict(MIX),
                          jax.devices())
    json.dumps(res)
    return res


def test_sound_run_is_correct(monkeypatch):
    res = _run_tiny(monkeypatch, 2**31 + 11)
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_compared"]["value"] >= 24
    assert list(res)[-1] == "checks"
    bench = registry.load(_paths.ROOT)
    cell = registry.workload(bench, "phi4-mini-3.8b.chat")
    assert set(res["metrics"]) == {
        m["name"] for m in registry.metrics_for(bench, cell, "end_to_end")}


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving import runner as runner_mod
    real = runner_mod.ModelRunner.decode_commit

    def altered(self, handle, stats):
        fresh = real(self, handle, stats)
        for task, i in fresh:
            task.output[i] = (task.output[i] + 1) % TINY["vocab"]
        return fresh

    monkeypatch.setattr(runner_mod.ModelRunner, "decode_commit", altered)
    res = _run_tiny(monkeypatch, 5)
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > LIMIT


def test_control_reads_where_the_served_tokens_do_not():
    """Greedy tokens of the reference itself read 0; the int8 control,
    at the same positions, puts other tokens first somewhere."""
    m = model.Model(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                    head_dim=32, d_ff=256, vocab=8192, rope_theta=1e4,
                    rope_fraction=1.0, norm_eps=1e-6)
    w = model.make_weights(m, 3, {"std": 0.02, "norm_scale": [0.8, 1.2]})
    rng = np.random.default_rng(0)
    seq = list(rng.integers(0, m.vocab, 16))
    for _ in range(200):
        z = reference.logits(w, m, np.asarray(seq, np.int32),
                             np.asarray([len(seq) - 1]))
        seq.append(int(z[0].argmax()))
    rec = types.SimpleNamespace(task=types.SimpleNamespace(
        prompt=np.asarray(seq[:16], np.int32), output=seq[16:]))
    got = check.compare(w, m, [rec], control=True)
    assert got["tokens"] == 200 and got["max_gap"] == got["mean_gap"] == 0.0
    assert got["control_max_gap"] > 0.0 and got["control_mean_gap"] > 0.0
    assert got["control_argmax_share"] < 1.0
