"""A whole run on the CPU at a test size (the look for a chip skipped),
sound and then with the timed path broken underneath: each fault a served
cell can have must turn ``correct`` false."""

import numpy as np
import pytest

import run
from conftest import FakeChip

SECONDS = 3.0


def _run(spec, seed=2**31 + 11, trace=False):
    return run.run_cell(spec, seed, SECONDS, trace, [FakeChip()])


def test_sound_run_is_correct_and_reports_every_metric(tiny_spec):
    out = _run(tiny_spec)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in tiny_spec["end_to_end"]}
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "tpu"


def test_traced_run_reads_the_tracer_metrics(tiny_spec, monkeypatch):
    # the CPU's profile holds no TPU plane: the trace's readers find
    # nothing to read and stay out of the line, the tracer's are read
    monkeypatch.setattr(run, "reduce_profile",
                        lambda profiler, data: (1.0, 2.0, {}))
    out = _run(tiny_spec, seed=7, trace=True)
    assert out["correct"], out["checks"]
    assert {"sched.batch_occupancy", "tick.mean_s", "step_mfu"} <= set(
        out["metrics"])
    assert "paged_decode_attention_roofline" not in out["metrics"]
    assert out["device"]["busy_s"] == 1.0


def test_the_fp8_control_fails_the_limits(tiny_spec):
    """The reference in float8 (one step below the served bfloat16), put
    in the program's place at the same served positions, comes out not
    correct through the harness's own comparison, where the program on
    the same sample keeps to the limits."""
    out = run.run_cell(tiny_spec, 2**31 + 5, SECONDS, False, [FakeChip()],
                       control=True)
    assert not out["correct"], out["checks"]
    limits = tiny_spec["config"]["correct"]
    assert all(out["program"][k] <= limits[k] for k in limits), out["program"]


def _alter_tokens(monkeypatch):
    from repro.serving import scheduler

    orig = scheduler.Scheduler._emit_event

    def emit(self, rid, idx, tok, lp):
        orig(self, rid, idx, (tok + 1) % self.cfg.vocab_size, lp)

    monkeypatch.setattr(scheduler.Scheduler, "_emit_event", emit)


def _state_unchanged(monkeypatch):
    from repro.serving import kv_pool

    monkeypatch.setattr(kv_pool.PagedKVPool, "update_from",
                        lambda self, new: None)


def _half_batch(monkeypatch):
    from repro.serving import scheduler

    orig = scheduler.paged_decode_step

    def step(params, cfg, tokens, caches, pos, opts):
        # every other slot, the first among them, decodes token 0 in place
        # of its own (a light load fills the lowest slots first)
        return orig(params, cfg, tokens.at[::2].set(0), caches, pos, opts)

    monkeypatch.setattr(scheduler, "paged_decode_step", step)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _half_batch])
def test_a_broken_path_is_not_correct(tiny_spec, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(tiny_spec)
    assert not out["correct"], out["checks"]
