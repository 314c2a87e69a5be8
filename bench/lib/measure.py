"""One run of one cell: set-up, the measured window, the metrics and the
correctness check, as the result line reports them."""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Tuple

from lib import check as C
from lib.harness import Session, counts, end_to_end, layer_record
from lib.peaks import peaks as device_peaks
from lib.registry import Cell


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
            smoke: bool = False, counter=None, fault=None) -> Tuple[Dict[str, Any], Dict[str, Any], List[str]]:
    """Returns (result line, counts printed before it, check lines).

    ``smoke`` runs the configuration's CPU-sized variant and ``fault``
    (a function of the engine) breaks the timed path underneath: both
    for the CPU tests only."""
    import jax

    sess = Session(cell, seed, smoke=smoke, counter=counter)
    sess.build()
    sess.warm_up()
    if fault is not None:
        fault(sess.engine)
    setup_compiles = counter.compiles if counter else None
    tracer = None
    if trace:
        from lib.trace import Tracer

        tracer = Tracer()
    setup_s = time.time() - t_start
    rec = sess.window(seconds, tracer)
    devices = jax.devices()
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    device: Dict[str, Any] = {"platform": devices[0].platform,
                              "kind": devices[0].device_kind, "count": len(devices),
                              "memory_peak_bytes": max(peak) if None not in peak else None}
    info = counts(rec)
    info.update(setup_s=setup_s, setup_compiles=setup_compiles,
                cache_hits=counter.cache_hits if counter else None, seed=seed,
                workload=cell.name)
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        from lib import trace as T

        try:
            reduced = T.reduce(tracer.path())
        finally:
            tracer.close()
        peaks = device_peaks(devices[0].device_kind)
        record = layer_record(rec, cell.work, sess.dims, peaks, reduced)
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = T.breakdown(reduced)
        info["modules_s"] = reduced["modules"]
    else:
        e2e = end_to_end(rec)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        info.update({k: v for k, v in e2e.items() if k not in metrics})

    judged, info["served_gaps"] = judge(sess, rec, seed)
    check_lines = [f"check {k}: {v} (limit {lim})" for k, (v, lim) in judged.items()]
    due_in = [i for i, d in rec.due.items() if d < rec.seconds]
    result: Dict[str, Any] = {
        "correct": C.verdict(judged),
        "attempted": len(due_in),
        "failed": info["shed"] + info["unserved"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": v, "limit": lim} for k, (v, lim) in judged.items()}
    return result, info, check_lines


def served_sample(sess: Session, rec, seed: int):
    """The sample's teacher-forced sequences, the count of non-finite
    logits and of token faults.  Frees the program's state, so that the
    reference runs after it."""
    e = sess.engine
    vocab = sess.model.spec.vocab
    completed = list(e.completed)
    gen = {i: r.gen_len for i, r in rec.requests.items()}
    picked = C.sample(completed, seed)
    prompts = {r["id"]: rec.requests[r["id"]].prompt_tokens(vocab) for r in picked}
    nonfinite = e.nonfinite_logits
    faults = C.token_faults(completed, gen, vocab)
    sess.engine = None
    del e
    gc.collect()
    t = sess.cell.traffic
    return C.sequences(picked, prompts, t.max_prompt + t.max_gen, t.max_gen), nonfinite, faults


def numbers(limits: Dict[str, float], gaps, nonfinite: int, faults: int) -> Dict[str, Tuple[Any, float]]:
    """Each number compared with its limit: those of the served tokens'
    gaps that the configuration sets a limit for, then the exact counts."""
    stats = C.gap_numbers(gaps) if gaps is not None and len(gaps) else {}
    out = {k: (stats.get(k), float(lim)) for k, lim in limits.items()}
    out.update(nonfinite_logits=(nonfinite, 0), token_faults=(faults, 0))
    return out


def judge(sess: Session, rec, seed: int):
    """The numbers compared, each with its limit, and every number of the
    served tokens' gaps."""
    seqs, nonfinite, faults = served_sample(sess, rec, seed)
    gaps = C.Reference(sess.cell.reference, sess.dims, seed).gaps(seqs) if seqs else None
    stats = C.gap_numbers(gaps) if gaps is not None else {}
    return numbers(sess.dims["limits"], gaps, nonfinite, faults), stats
