"""The control at the smoke size: the reference computed in bfloat16, put
in the program's place, fails the limit that the program meets.

The engine serves a fixed set of requests from the cell's traffic (no
wall clock, so the sample does not depend on this host's speed); the
program's served tokens are then judged as a benchmark run judges them,
and the control is read at the same positions of the same sequences.
At the cells' own sizes the readings come from ``bench/calibrate.py``
on the chip.
"""
import itertools

import pytest

from lib import check as C
from lib.harness import Session
from lib.measure import numbers
from lib.registry import load_cell


def _serve(sess, requests):
    e = sess.engine
    pending = list(requests)
    while pending or any(s is not None for s in e.slots):
        while pending and None in e.slots:
            e._join(pending.pop(0))
        e._decode_step()
    return list(e.completed)


@pytest.mark.parametrize("seed", [1, 5, 2**31 + 17, 2**32 + 3])
@pytest.mark.parametrize("workload", ["qwen3-1.7b.decode-batch"])
def test_bfloat16_control_fails_where_the_program_passes(workload, seed):
    cell = load_cell(workload)
    sess = Session(cell, seed, smoke=True)
    sess.build()
    t = cell.traffic
    requests = list(itertools.islice(t.closed_stream(seed), 12))
    completed = _serve(sess, requests)
    vocab = sess.model.spec.vocab
    by_id = {r.id: r for r in requests}
    picked = C.sample(completed, seed)
    prompts = {r["id"]: by_id[r["id"]].prompt_tokens(vocab) for r in picked}
    seqs = C.sequences(picked, prompts, t.max_prompt + t.max_gen, t.max_gen)
    sess.engine = None
    ref = C.Reference(cell.reference, sess.dims, seed)
    limits = sess.dims["limits"]
    assert C.verdict(numbers(limits, ref.gaps(seqs), 0, 0))
    assert not C.verdict(numbers(limits, ref.control_gaps(seqs), 0, 0))
