"""A whole run of a cell at a CPU size, past the harness's look for a chip:
it comes out correct, and comes out not correct with the timed path broken
underneath (a token altered where the sampler produces it; decode steps
that leave the KV state unchanged).  The float8 control, kept here at a
size a test run holds, reads well above the program on the same tokens.

The tiny model's limits are its own, set like the cells' from its readings
(program: widest gap 0.0, logit error 0.03; float8 control: logit error
0.19; the KV fault: logit error 0.35)."""
import time

import pytest

from conftest import tiny_cell

from chipbench import correct, harness, spec

TINY_LIMITS = {"widest_gap": 0.5, "logit_error": 0.1}


def _run(seed=11, workload="gpt2-1.5b.chat"):
    cell = tiny_cell(workload)
    cell.config["limits"] = dict(TINY_LIMITS)
    out = harness.run_cell(cell, seed, 1.5, False,
                           t_process=time.perf_counter(), require_chip=False,
                           log=lambda m: None)
    out["_cell"] = cell
    out["_rundata"] = out.pop("_run")
    return out


@pytest.fixture(scope="module")
def clean():
    return _run()


def test_clean_run_is_correct(clean):
    assert clean["correct"], clean["checks"]
    assert clean["checks"]["tokens_compared"]["value"] > 50
    assert set(clean["metrics"]) == set(clean["_cell"].end_to_end)
    assert set(clean["metrics"]) >= {"tokens_per_s", "setup_s"}
    assert clean["attempted"] >= 2 and clean["failed"] == 0
    assert [k for k in clean if not k.startswith("_")][-1] == "checks"


def test_docqa_run_reports_the_gap_tail():
    out = _run(workload="opt-66b-s4.docqa")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "engine.itl_p95_ms" in out["_cell"].per_layer
    assert spec.reader("engine.itl_p95_ms")(out["_rundata"]) > 0


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    import repro.serving.sampling as sampling
    greedy, calls = sampling.greedy, [0]

    def altered(logits, step=0):
        tok = greedy(logits, step)
        calls[0] += 1
        return (tok + 1) % logits.shape[-1] if calls[0] % 7 == 0 else tok

    monkeypatch.setattr(sampling, "greedy", altered)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["widest_gap"]["value"] > TINY_LIMITS["widest_gap"]


def test_decode_leaving_kv_unchanged_is_not_correct(monkeypatch):
    from repro.core.worker import StageWorker
    decode = StageWorker.decode_paged_batch

    def unchanged(self, *a, **k):
        self.pages.write_window = lambda *a, **k: []
        try:
            return decode(self, *a, **k)
        finally:
            del self.pages.write_window

    monkeypatch.setattr(StageWorker, "decode_paged_batch", unchanged)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["logit_error"]["value"] > TINY_LIMITS["logit_error"]


def test_float8_control_fails_the_limits(clean):
    c, items = clean["_compared"]
    ctl = correct.control(c.ref, c.params, c.arch, c.cell.config, items,
                          int(c.arch["max_seq_len"]))
    assert ctl["program_logit_error"] == pytest.approx(
        clean["checks"]["logit_error"]["value"])
    assert ctl["control_logit_error"] > TINY_LIMITS["logit_error"] \
        > ctl["program_logit_error"]
    assert ctl["control_logit_error"] > 3 * ctl["program_logit_error"]
    # through the harness's own checks and verdict, the control fails
    chk = correct.control_checks(ctl, TINY_LIMITS)
    assert chk["tokens_compared"]["value"] == \
        clean["checks"]["tokens_compared"]["value"]
    assert not correct.passed(chk)
