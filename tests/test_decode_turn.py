"""The decode loop's schedule, written down.

A scripted engine (a clock that stands still, fixed seeds, the loop
thread released one turn at a time) serves the same few requests under
each schedule the engine has, and every turn is held to a literal: the
``_compiled[...]`` keys it called in order (a step's with the slots it
stepped and the model version, a prompt program's with the rows it
took), the counters it moved, and what it left unread on the device.

The requests force: a whole-prompt prefill (or, with ``prefill_chunk``,
its one chunk), a prompt of three chunks whose final chunk has another
prompt's chunk queued behind it in the same turn, a slot whose budget
ends mid-horizon, a slot that only the READ finds ended (its finite flag
is falsified on the way back: nothing of the schedule depends on what
the model computes), and two model versions alive at once.

``SCHEDULE`` was recorded from the commit before the three decode loops
became one turn (``python tests/test_decode_turn.py`` prints it), so it
is the proof that the turn kept their schedules.  A change of schedule
changes this literal and nothing else here.
"""

import threading

import numpy as np
import pytest

from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.transformer import ShardedTransformerLM
from deeplearning4j_tpu.serving import DecodeEngine, PoisonInputError

VOCAB, MAXLEN, PAGE, SLOTS, CHUNK = 48, 64, 4, 4, 8
CASES = [(1, None), (1, CHUNK), (4, None), (4, CHUNK), (1, None, True)]
#: prompt tokens of the request that the read finds ended
POISONED = 3
#: which output of a program holds a step's finite flags
FINITE_FLAGS = {"sample": 1, "step_multi": 3, "spec_accept": 2}
COUNTERS = ("prefills", "prefill_chunks", "chunked_prefills", "chunk_turns",
            "chunk_turns_multi", "decode_steps", "steps_ahead", "step_drains",
            "overrun_slot_steps", "fused_dispatches", "tokens_per_dispatch",
            "spec_steps", "tokens_out", "poison_isolated",
            "max_token_stops")


def _lm():
    import jax

    mesh = build_mesh({"data": 1, "model": 1, "seq": 1, "pipe": 1},
                      jax.devices()[:1])
    return ShardedTransformerLM(vocab_size=VOCAB, n_layers=1, d_model=16,
                                n_heads=2, max_len=MAXLEN, mesh=mesh, seed=11)


@pytest.fixture(scope="module")
def lm():
    return _lm()


def _prompt(n, k):
    return [1 + (i * k) % (VOCAB - 1) for i in range(n)]


class _Turns:
    """Holds the engine's loop at the head of each turn and logs what a
    turn calls.  The SECOND decode dispatch that steps the request whose
    prompt has ``POISONED`` tokens comes back with its step ``j`` (of the
    horizon; the plain step's one) not finite."""

    def __init__(self, eng, j):
        self.eng, self.j = eng, j
        self.events, self.stepped, self.hit = [], 0, None
        self.go, self.parked = threading.Semaphore(0), threading.Semaphore(0)
        self.tags = {}
        for key, exe in list(eng._compiled.items()):
            eng._compiled[key] = self._logged(key, exe)
        self._admit = eng._admit_some

        def gated(turn=None):
            self.parked.release()
            self.go.acquire()
            return self._admit(turn)
        eng._admit_some = gated
        assert self.parked.acquire(timeout=60), "the loop never came round"

    def _logged(self, key, exe):
        name = ":".join(str(p) for p in key)

        def call(*args):
            out = exe(*args)
            if key[0] in ("step", "step_multi", "spec_step"):
                group = np.flatnonzero(args[6])
                tag = self.tags.get(id(args[0]), "?")
                self.events.append(
                    f"{name}@{tag}[{','.join(str(i) for i in group)}]")
                self.hit = None
                for i in group:
                    if self.eng._slots[i].n_prompt == POISONED:
                        self.stepped += 1
                        self.hit = i if self.stepped == 2 else None
            elif key[0] == "prefill":
                self.events.append(f"{name}/{int(args[5])}")
            elif key[0] == "prefill_at":
                self.events.append(
                    f"{name}/{int(args[6])}+{int(args[5])}")
            else:
                self.events.append(name)
            at = FINITE_FLAGS.get(key[0])
            if at is not None and self.hit is not None:
                # the program that hands the step's finite flags back
                out, slot, self.hit = tuple(out), self.hit, None
                fin = out[at]
                fin = fin.at[(self.j, slot) if fin.ndim == 2 else slot
                             ].set(False)
                out = out[:at] + (fin,) + out[at + 1:]
            return out
        return call

    def turn(self):
        """Run one turn of the loop; what it called and what it moved."""
        m = self.eng.metrics
        before = {k: m.counter_value(k) for k in COUNTERS}
        self.events = []
        self.go.release()
        assert self.parked.acquire(timeout=120), "a turn never ended"
        moved = [f"{k}+{int(m.counter_value(k) - before[k])}"
                 for k in COUNTERS if m.counter_value(k) != before[k]]
        left = []
        if self.eng._flight is not None:
            left.append("flight")
        if self.eng._chunk_inflight:
            left.append(f"chunks:{len(self.eng._chunk_inflight)}")
        return " | ".join(" ".join(part) for part in
                          (self.events, moved, left)).rstrip(" |")

    def release(self):
        self.eng._admit_some = self._admit
        self.go.release()


def run(lm, horizon, chunk, draft=False):
    """The script under one schedule: a line a turn."""
    import jax

    eng = DecodeEngine(lm, max_slots=SLOTS, page_size=PAGE, max_len=MAXLEN,
                       prompt_buckets=(8, 64), decode_horizon=horizon,
                       prefill_chunk=chunk, clock=lambda: 0.0,
                       **(dict(draft_model=lm, speculate_k=2) if draft
                          else {})).load()
    turns = _Turns(eng, j=1 if horizon > 1 else 0)
    per = 3 if draft else horizon       # tokens a slot gets a dispatch
    turns.tags[id(lm.params)] = "v0"
    # a second version of the same values: what the model computes must
    # not decide a turn (speculation accepts by it)
    v1 = jax.tree_util.tree_map(lambda a: a * 1, lm.params)
    turns.tags[id(v1)] = "v1"
    futs, lines = {}, []

    def submit(name, prompt, **kw):
        futs[name] = eng.generate_async(prompt, **kw)
    try:
        for t in range(40):
            if t == 0:
                submit("A", _prompt(5, 3), max_new_tokens=10 * per,
                       echo_logits=True)
            if t == 2:
                submit("C", _prompt(38, 7), max_new_tokens=3,
                       temperature=0.8, top_k=5, seed=3)
                submit("B", _prompt(20, 5), max_new_tokens=per + 2)
            if t == 6:
                submit("D", _prompt(POISONED, 11), max_new_tokens=20)
            if t == 9:
                eng.swap_model(v1, "v1")
                submit("E", _prompt(4, 13), max_new_tokens=per + 3,
                       echo_logits=True)
            lines.append(turns.turn())
            if t > 9 and all(f.done() for f in futs.values()) \
                    and not lines[-1]:
                break
    finally:
        turns.release()
        eng.shutdown()
    ends = {}
    for name, f in futs.items():
        try:
            res = f.result(timeout=60)
            ends[name] = f"{res.finish_reason}:{len(res.tokens)}"
            if res.logits is not None:
                assert len(res.logits) == len(res.tokens)
        except PoisonInputError:
            ends[name] = "poison"
    lines.append(" ".join(f"{k}={v}" for k, v in sorted(ends.items())))
    return lines


SCHEDULE = {
    (1, None): [
        'prefill:8/5 sample1 step@v0[0] sample | prefills+1 decode_steps+1 tokens_out+1 | flight',
        'join step@v0[0] sample | decode_steps+1 steps_ahead+1 tokens_out+1 | flight',
        'prefill:64/38 sample1 prefill:64/20 sample1 join step@v0[0,1,2] sample | prefills+2 decode_steps+1 steps_ahead+1 tokens_out+3 | flight',
        'join step@v0[0,1,2] sample | decode_steps+1 steps_ahead+1 tokens_out+3 | flight',
        'join step@v0[0] sample | decode_steps+1 steps_ahead+1 tokens_out+3 max_token_stops+2 | flight',
        'join step@v0[0] sample | decode_steps+1 steps_ahead+1 tokens_out+1 | flight',
        'prefill:8/3 sample1 join step@v0[0,1] sample | prefills+1 decode_steps+1 steps_ahead+1 tokens_out+2 | flight',
        'join step@v0[0,1] sample | decode_steps+1 steps_ahead+1 tokens_out+2 | flight',
        'join step@v0[0,1] sample scrub | decode_steps+1 steps_ahead+1 tokens_out+1 poison_isolated+1 | flight',
        'prefill:8/4 sample1 step@v1[1] sample | prefills+1 decode_steps+1 step_drains+1 overrun_slot_steps+1 tokens_out+3 max_token_stops+1',
        'step@v1[1] sample | decode_steps+1 | flight',
        'join step@v1[1] sample | decode_steps+1 steps_ahead+1 tokens_out+1 | flight',
        ' | tokens_out+1 max_token_stops+1',
        '',
        'A=max_tokens:10 B=max_tokens:3 C=max_tokens:3 D=poison E=max_tokens:4',
    ],
    (1, 8): [
        'prefill_at:8/0+5 sample1 step@v0[0] sample | prefills+1 prefill_chunks+1 chunk_turns+1 decode_steps+1 tokens_out+1 | flight',
        'join step@v0[0] sample | decode_steps+1 steps_ahead+1 tokens_out+1 | flight',
        'prefill_at:8/0+8 join step@v0[0] sample | prefill_chunks+1 chunk_turns+1 decode_steps+1 steps_ahead+1 tokens_out+1 | flight',
        'prefill_at:8/0+8 prefill_at:8/8+8 join step@v0[0] sample | prefill_chunks+2 chunk_turns+1 chunk_turns_multi+1 decode_steps+1 steps_ahead+1 tokens_out+1 | flight',
        'prefill_at:8/8+8 prefill_at:8/16+8 join step@v0[0] sample | prefill_chunks+2 chunk_turns+1 chunk_turns_multi+1 decode_steps+1 steps_ahead+1 tokens_out+1 | flight',
        'prefill_at:8/16+4 sample1 prefill_at:8/24+8 join step@v0[0,2] sample | prefills+1 prefill_chunks+2 chunked_prefills+1 chunk_turns+1 chunk_turns_multi+1 decode_steps+1 steps_ahead+1 tokens_out+2 | flight',
        'prefill_at:8/0+3 sample1 prefill_at:8/32+6 sample1 join step@v0[0,1,2,3] sample | prefills+2 prefill_chunks+2 chunked_prefills+1 chunk_turns+1 chunk_turns_multi+1 decode_steps+1 steps_ahead+1 tokens_out+4 | flight',
        'join step@v0[0,1,3] sample | decode_steps+1 steps_ahead+1 tokens_out+4 max_token_stops+1 | flight',
        'join step@v0[0,3] sample scrub | decode_steps+1 steps_ahead+1 tokens_out+2 poison_isolated+1 max_token_stops+1 | flight',
        'prefill_at:8/0+4 sample1 step@v1[1] sample | prefills+1 prefill_chunks+1 chunk_turns+1 decode_steps+1 step_drains+1 overrun_slot_steps+1 tokens_out+3 max_token_stops+1',
        'step@v1[1] sample | decode_steps+1 | flight',
        'join step@v1[1] sample | decode_steps+1 steps_ahead+1 tokens_out+1 | flight',
        ' | tokens_out+1 max_token_stops+1',
        '',
        'A=max_tokens:10 B=max_tokens:3 C=max_tokens:3 D=poison E=max_tokens:4',
    ],
    (4, None): [
        'prefill:8/5 sample1 step_multi:4@v0[0] | prefills+1 decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+5',
        'step_multi:4@v0[0] | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+4',
        'prefill:64/38 sample1 prefill:64/20 sample1 step_multi:4@v0[0,1,2] | prefills+2 decode_steps+1 fused_dispatches+1 tokens_per_dispatch+10 tokens_out+12 max_token_stops+1',
        'step_multi:4@v0[0,2] | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+5 tokens_out+5 max_token_stops+1',
        'step_multi:4@v0[0] | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+4',
        'step_multi:4@v0[0] | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+4',
        'prefill:8/3 sample1 step_multi:4@v0[0,1] | prefills+1 decode_steps+1 fused_dispatches+1 tokens_per_dispatch+8 tokens_out+9',
        'step_multi:4@v0[0,1] scrub | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+5 tokens_out+5 poison_isolated+1',
        'step_multi:4@v0[0] | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+4',
        'prefill:8/4 sample1 step_multi:4@v0[0] step_multi:4@v1[1] | prefills+1 decode_steps+2 fused_dispatches+2 tokens_per_dispatch+7 tokens_out+8 max_token_stops+1',
        'step_multi:4@v1[1] | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+2 tokens_out+2 max_token_stops+1',
        '',
        'A=max_tokens:40 B=max_tokens:6 C=max_tokens:3 D=poison E=max_tokens:7',
    ],
    (4, 8): [
        'prefill_at:8/0+5 sample1 | prefills+1 prefill_chunks+1 chunk_turns+1 tokens_out+1',
        'step_multi:4@v0[0] | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+4',
        'step_multi:4@v0[0] prefill_at:8/0+8 | chunk_turns+1 decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+4 | chunks:1',
        'step_multi:4@v0[0] prefill_at:8/0+8 prefill_at:8/8+8 | prefill_chunks+1 chunk_turns+1 chunk_turns_multi+1 decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+4 | chunks:2',
        'step_multi:4@v0[0] prefill_at:8/8+8 prefill_at:8/16+8 | prefill_chunks+2 chunk_turns+1 chunk_turns_multi+1 decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+4 | chunks:2',
        'step_multi:4@v0[0] prefill_at:8/16+4 sample1 prefill_at:8/24+8 | prefills+1 prefill_chunks+3 chunked_prefills+1 chunk_turns+1 chunk_turns_multi+1 decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+5 | chunks:1',
        'step_multi:4@v0[0,2] prefill_at:8/0+3 sample1 prefill_at:8/32+6 sample1 | prefills+2 prefill_chunks+3 chunked_prefills+1 chunk_turns+1 chunk_turns_multi+1 decode_steps+1 fused_dispatches+1 tokens_per_dispatch+8 tokens_out+10',
        'step_multi:4@v0[0,1,2,3] | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+11 tokens_out+11 max_token_stops+2',
        'step_multi:4@v0[0,3] scrub | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+5 tokens_out+5 poison_isolated+1',
        'step_multi:4@v0[0] prefill_at:8/0+4 sample1 | prefills+1 prefill_chunks+1 chunk_turns+1 decode_steps+1 fused_dispatches+1 tokens_per_dispatch+4 tokens_out+5',
        'step_multi:4@v0[0] step_multi:4@v1[1] | decode_steps+2 fused_dispatches+2 tokens_per_dispatch+7 tokens_out+7 max_token_stops+1',
        'step_multi:4@v1[1] | decode_steps+1 fused_dispatches+1 tokens_per_dispatch+2 tokens_out+2 max_token_stops+1',
        '',
        'A=max_tokens:40 B=max_tokens:6 C=max_tokens:3 D=poison E=max_tokens:7',
    ],
    (1, None, True): [
        'prefill:8/5 sample1 draft_prefill:8 draft_step propose draft_step propose spec_step@v0[0] spec_accept draft_step | prefills+1 decode_steps+1 spec_steps+1 tokens_out+4',
        'draft_step propose draft_step propose spec_step@v0[0] spec_accept draft_step | decode_steps+1 spec_steps+1 tokens_out+3',
        'prefill:64/38 sample1 draft_prefill:64 prefill:64/20 sample1 draft_prefill:64 draft_step propose draft_step propose spec_step@v0[0,1,2] spec_accept draft_step | prefills+2 decode_steps+1 spec_steps+1 tokens_out+10 max_token_stops+1',
        'draft_step propose draft_step propose spec_step@v0[0,2] spec_accept draft_step | decode_steps+1 spec_steps+1 tokens_out+4 max_token_stops+1',
        'draft_step propose draft_step propose spec_step@v0[0] spec_accept draft_step | decode_steps+1 spec_steps+1 tokens_out+3',
        'draft_step propose draft_step propose spec_step@v0[0] spec_accept draft_step | decode_steps+1 spec_steps+1 tokens_out+3',
        'prefill:8/3 sample1 draft_prefill:8 draft_step propose draft_step propose spec_step@v0[0,1] spec_accept draft_step | prefills+1 decode_steps+1 spec_steps+1 tokens_out+7',
        'draft_step propose draft_step propose spec_step@v0[0,1] spec_accept scrub draft_scrub draft_step | decode_steps+1 spec_steps+1 tokens_out+3 poison_isolated+1',
        'draft_step propose draft_step propose spec_step@v0[0] spec_accept draft_step | decode_steps+1 spec_steps+1 tokens_out+3',
        'prefill:8/4 sample1 draft_prefill:8 draft_step propose draft_step propose spec_step@v0[0] spec_accept draft_step propose draft_step propose spec_step@v1[1] spec_accept draft_step | prefills+1 decode_steps+2 spec_steps+2 tokens_out+6 max_token_stops+1',
        'draft_step propose draft_step propose spec_step@v1[1] spec_accept | decode_steps+1 spec_steps+1 tokens_out+2 max_token_stops+1',
        '',
        'A=max_tokens:30 B=max_tokens:5 C=max_tokens:3 D=poison E=max_tokens:6',
    ],
}


@pytest.mark.parametrize("case", CASES, ids=str)
def test_every_turn_calls_what_the_schedule_says(lm, case):
    assert run(lm, *case) == SCHEDULE[case]


if __name__ == "__main__":
    model = _lm()
    print("SCHEDULE = {")
    for case in CASES:
        print(f"    {case!r}: [")
        for line in run(model, *case):
            print(f"        {line!r},")
        print("    ],")
    print("}")
