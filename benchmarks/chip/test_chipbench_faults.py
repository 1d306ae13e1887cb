"""``correct`` comes out false when the timed path is broken underneath:
each fault a cell can have is planted in the program, and a whole tiny
run goes through the harness on the CPU. The lower-precision control is
read at the same size and must read above the sound program."""
import numpy as np
import pytest

from chipbench import calibrate, harness
from chipbench.testing import run_tiny, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("faults")))


def _unchanged(params_k, stats_k, *a, **kw):
    return params_k, stats_k


def _half_batch(real):
    def epoch(cfg, params_k, stats_k, xb, tb, mb, lr, **kw):
        half = xb.shape[2] // 2
        return real(cfg, params_k, stats_k, xb[:, :, :half], tb[:, :, :half],
                    mb, lr, **kw)
    return epoch


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_training_fault_is_not_correct(root, monkeypatch, fault):
    from repro.core import executor
    real = executor._stacked_epoch
    monkeypatch.setattr(executor, "_stacked_epoch",
                        (lambda cfg, p, s, *a, **kw: _unchanged(p, s))
                        if fault == "unchanged" else _half_batch(real))
    result, _, _ = run_tiny(root, "elm-3c9c-k4-skew")
    assert result["correct"] is False
    assert any(v["value"] > v["limit"]
               for v in result["compared"].values())


def test_serving_answer_altered_is_not_correct(root, monkeypatch):
    from repro.serve.engine import BucketedScorer
    real = BucketedScorer.score_block

    def swapped(self, x):
        return np.roll(real(self, x), 1, axis=-1)   # another class's score

    monkeypatch.setattr(BucketedScorer, "score_block", swapped)
    result, _, _ = run_tiny(root, "serve-3c9c-k4-poisson")
    assert result["correct"] is False


def test_serving_label_altered_is_not_correct(root, monkeypatch):
    """The member scores stay right; the combine answers the next class."""
    from repro.serve import scheduler
    real = scheduler.combine_block

    def shifted(scores, *a, **kw):
        return (real(scores, *a, **kw) + 1) % scores.shape[-1]

    monkeypatch.setattr(scheduler, "combine_block", shifted)
    result, _, _ = run_tiny(root, "serve-3c9c-k4-poisson")
    assert result["correct"] is False
    shown = result["compared"]
    assert shown["score_gap"]["value"] == 0
    assert shown["label_gap"]["value"] > shown["label_gap"]["limit"]


@pytest.mark.parametrize("workload", ["elm-3c9c-k4-skew",
                                      "serve-3c9c-k4-poisson"])
def test_control_reads_above_the_sound_program(root, workload):
    """At this size the sound program and the reference agree to the bit
    on the CPU; the control, three bf16 passes, does not."""
    env = harness.load_env(root, workload, 0, 0.0, False)
    out = calibrate.readings(env, [2 ** 31 + 3], ["control"],
                             log=lambda *a: None)
    control = list(out.values())[0]["control"]
    assert isinstance(control.pop("correct"), bool)
    assert max(control.values()) > 0
    sound, _, _ = run_tiny(root, workload)
    assert all(v["value"] == 0 for v in sound["compared"].values())

