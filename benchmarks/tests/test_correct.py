"""That ``correct`` can come out false: each configuration's control (its
reference at the nearest lower precision, put in the program's place)
fails the configuration's limits at a size a test run can hold, and a
run whose timed path is broken underneath prints ``correct: false``.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

The same controls were read on the chip at the cells' own sizes (PERF.md,
section 2, gives the readings the limits were set from).
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest           # noqa: E402

from benchmarks import harness           # noqa: E402
from benchmarks.tests import rehearsal   # noqa: E402

ARGS = ["--seed", "3000000019", "--seconds", "3", "--trace", "0"]


def run(cell, capsys):
    rc = harness.main(["--workload", cell] + ARGS,
                      rehearsal=rehearsal.CELLS[cell])
    out = capsys.readouterr().out.strip().splitlines()
    compared = {}
    for ln in out:
        if ln.startswith("bench: compared: {"):
            c = json.loads(ln[len("bench: compared: "):])
            compared[c["number"]] = c
    return rc, json.loads(out[-1]), compared


@pytest.mark.parametrize("cell", sorted(rehearsal.CELLS))
def test_the_control_fails_the_limits_and_the_program_meets_them(cell):
    """The reference in the lower precision fails at least one of the
    cell's numbers; the program's own path meets every one."""
    _, opened, _ = harness.open_cell(cell, 2147483659, 3.0, False,
                                     rehearsal.CELLS[cell])
    runner = harness.load_runner(opened.config["runner"])
    state = runner.setup(opened, {})
    if opened.mix["kind"] != "train":
        runner.window(opened, state, harness.Tracer(False, ""))
    served = runner.release(opened, state)
    res = runner.compare(opened, served, with_control=True)
    limits = opened.config["limits"]
    assert res["numbers"] and set(res["numbers"]) <= set(limits)
    for name, value in res["numbers"].items():
        assert value <= limits[name], (name, value)
    failed = [n for n, v in res["control"].items() if v > limits[n]]
    assert failed, res["control"]


def test_a_train_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    from deeplearning4j_tpu.parallel import ShardedTransformerLM

    real = ShardedTransformerLM.fit_batch

    def frozen(self, tokens, targets):
        import jax
        keep = jax.tree_util.tree_map(lambda a: a.copy(),
                                      (self.params, self.opt_state))
        loss = real(self, tokens, targets)
        self.params, self.opt_state = keep
        return loss

    monkeypatch.setattr(ShardedTransformerLM, "fit_batch", frozen)
    rc, result, compared = run("gpt2-medium.train-1k", capsys)
    assert rc == 0 and result["correct"] is False
    assert not compared["delta_norm_gap"]["ok"]


def test_a_train_step_that_leaves_out_part_of_the_batch_is_not_correct(
        capsys, monkeypatch):
    from deeplearning4j_tpu.parallel import ShardedTransformerLM

    real = ShardedTransformerLM.fit_batch

    def partial(self, tokens, targets):
        tokens, targets = tokens.copy(), targets.copy()
        tokens[-1], targets[-1] = tokens[0], targets[0]   # one row never seen
        return real(self, tokens, targets)

    monkeypatch.setattr(ShardedTransformerLM, "fit_batch", partial)
    rc, result, compared = run("gpt2-medium.train-1k", capsys)
    assert rc == 0 and result["correct"] is False
    assert not compared["grad_error"]["ok"]


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from deeplearning4j_tpu.serving import DecodeEngine

    real = DecodeEngine._record_token

    def altered(self, i, token, finite, logits_row, now):
        s = self._slots[i]
        if s is not None and s.n_out == 3:        # the fourth token of a request
            token = (int(token) + 1) % self.program.vocab_size
        return real(self, i, token, finite, logits_row, now)

    monkeypatch.setattr(DecodeEngine, "_record_token", altered)
    rc, result, compared = run("gpt2-large.chat-closed8", capsys)
    assert rc == 0 and result["correct"] is False
    assert not compared["served_logit_gap"]["ok"]


def test_served_logits_off_by_a_hundredth_are_not_correct(capsys, monkeypatch):
    """The tokens stay the reference's best, so only the logits tell."""
    from deeplearning4j_tpu.serving import DecodeEngine

    real = DecodeEngine._record_token

    def scaled(self, i, token, finite, logits_row, now):
        if logits_row is not None:
            logits_row = logits_row * 1.01
        return real(self, i, token, finite, logits_row, now)

    monkeypatch.setattr(DecodeEngine, "_record_token", scaled)
    rc, result, compared = run("gpt2-large.chat-closed8", capsys)
    assert rc == 0 and result["correct"] is False
    assert compared["served_logit_gap"]["ok"]
    assert not compared["served_logit_mse"]["ok"]


def test_a_window_that_finishes_no_request_is_not_correct(capsys, monkeypatch):
    from deeplearning4j_tpu.serving import DecodeEngine

    real = DecodeEngine.generate_async
    calls = {"n": 0}

    def never(self, prompt, **kw):
        calls["n"] += 1
        if calls["n"] <= 6:                       # the warm-up requests pass
            return real(self, prompt, **kw)
        from concurrent.futures import Future
        return Future()

    monkeypatch.setattr(DecodeEngine, "generate_async", never)
    rc, result, _ = run("gpt2-large.chat-closed8", capsys)
    assert rc == 0 and result["correct"] is False
    assert result["attempted"] == result["failed"] == 8     # never came back
