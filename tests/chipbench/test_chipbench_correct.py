"""The check that decides ``correct``, driven through the harness on the
CPU at a tiny size (``ref`` kernels): a sound run passes; the control (the
reference in float8 put in the program's place) and each fault planted in
the timed path (a token altered where it is produced, a decode or chunk
step that returns the page pool unchanged) fail.

At this size the program's widest logit gap reads 0 to 0.0032 and the
control's 0.030 to 0.147 (five seeds), so the tiny cells hold the limit
0.01.
"""
from pathlib import Path

import pytest
from chipbench_tiny import tiny_root

from chipbench import harness

LIMIT = 0.01
SEED = 2**31 + 5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(Path(tmp_path_factory.mktemp("tiny")), LIMIT)


def _job(root, name, seed):
    cell = harness.load_cell(name, root=root)
    ctx = harness.RunContext(cell=cell, seed=seed, seconds=3.0,
                             backend="ref")
    ctx.model_cfg = harness.model_config(cell.cfg)
    job = harness.load_module(harness.HERE / "jobs" / "serve.py").Job(ctx)
    job.setup(3.0)
    job.window(3.0)
    job.observe()
    job.free()
    return job


@pytest.mark.parametrize("name", ["tiny.batch", "tiny.chat"])
def test_sound_run_is_correct(root, name):
    cell = harness.load_cell(name, root=root)
    res = harness.run_cell(cell, SEED, 3.0, False, backend="ref",
                           require_tpu=False, log=lambda *_: None)
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == ["widest_logit_gap", "step_failures",
                                   "page_audit_issues"]
    assert res["checks"]["widest_logit_gap"]["value"] <= LIMIT
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want and "setup_s" in want
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_in_float8_is_not_correct(root):
    cell = harness.load_cell("tiny.batch", root=root)
    res = harness.run_cell(cell, SEED, 3.0, False, backend="ref",
                           require_tpu=False, control=True,
                           log=lambda *_: None)
    assert res["correct"] is False
    assert res["checks"]["widest_logit_gap"]["value"] > LIMIT


def test_control_and_program_are_judged_on_the_same_tokens(root):
    job = _job(root, "tiny.batch", SEED)
    ok, checks = harness.judge(job)
    assert ok and job.checked_tokens >= 20
    bad, ctl = harness.judge(job, control=True)
    assert not bad and ctl[0][1] > LIMIT >= checks[0][1]
    assert len(job.detail) == len(job.sample())


def test_token_altered_where_produced_is_not_correct(root, monkeypatch):
    from repro.launch import steps

    real = steps.sample_token_guarded

    def off_by_one(logits, key, temperature):
        return (real(logits, key, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(steps, "sample_token_guarded", off_by_one)
    cell = harness.load_cell("tiny.batch", root=root)
    res = harness.run_cell(cell, SEED, 3.0, False, backend="ref",
                           require_tpu=False, log=lambda *_: None)
    assert not res["correct"]
    assert res["checks"]["widest_logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("step", ["forward_decode_paged",
                                  "forward_prefill_chunk"])
def test_step_returning_its_pages_unchanged_is_not_correct(root, monkeypatch,
                                                           step):
    """A decode or chunk step that leaves the KV page pool as it found it:
    later tokens attend to a history that was never written."""
    from repro.launch import steps

    real = getattr(steps, step)

    def unchanged(params, cfg, batch, pools, *args):
        logits, _ = real(params, cfg, batch, pools, *args)
        return logits, pools

    monkeypatch.setattr(steps, step, unchanged)
    cell = harness.load_cell("tiny.batch", root=root)
    res = harness.run_cell(cell, SEED, 3.0, False, backend="ref",
                           require_tpu=False, log=lambda *_: None)
    assert not res["correct"]
    assert res["checks"]["widest_logit_gap"]["value"] > LIMIT
