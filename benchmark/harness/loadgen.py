"""The load generator: an open loop on a schedule, or a closed loop of
clients, against any ``stream_fn(request) -> iterator of tokens``.

It runs in the process that owns the chip (no server outlives a run):
one sender on the schedule and one light consumer thread per request in
flight. Times are seconds relative to the window's start (``t0``), on
`time.perf_counter`.

In local mode ``stream_fn`` returns a generator, so the request enters
the engine at the consumer's first ``next()``: that moment is the send
time, and ``sent - due`` says whether the generator kept up.
"""

from __future__ import annotations

import threading
import time


def _record(req, due: float) -> dict:
    return {"due": due, "sent": None, "first": None, "last": None,
            "n_want": req.answer_len, "n_got": 0, "done": False,
            "error": None, "timed": False, "token_times": [],
            "bad_ids": 0}


def _consume(stream_fn, req, rec: dict, t0: float, vocab: int) -> None:
    try:
        it = stream_fn({"prompt_ids": list(req.prompt_ids),
                        "max_new_tokens": req.answer_len})
        rec["sent"] = time.perf_counter() - t0
        for tok in it:
            now = time.perf_counter() - t0
            if rec["first"] is None:
                rec["first"] = now
            rec["last"] = now
            rec["token_times"].append(now)
            rec["n_got"] += 1
            if not 0 <= tok < vocab:
                rec["bad_ids"] += 1
        rec["done"] = True
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        rec["error"] = repr(e)[:200]
        now = time.perf_counter() - t0
        for k in ("sent", "first", "last"):    # it waited until it failed
            rec[k] = now if rec[k] is None else rec[k]


def _finish(records, threads, deadline: float, t0: float) -> None:
    """Wait for the consumers until ``deadline``; what is still running
    then has failed, and its missing times are the deadline's."""
    for th in threads:
        th.join(max(0.0, deadline - time.perf_counter()))
    cut = deadline - t0
    for rec in records:
        if not rec["done"] and rec["timed"]:
            rec["error"] = rec["error"] or "ran past the drain"
            for k in ("sent", "first", "last"):
                rec[k] = cut if rec[k] is None else rec[k]


def open_loop(stream_fn, requests, *, seconds: float, drain_s: float,
              vocab: int, t0: float, on_window_end=lambda: None) -> list:
    """Send each request at ``t0 + due_s`` whatever the server does.
    Requests due in [0, seconds) are timed. Returns one record each."""
    records, threads = [], []
    for req in requests:
        wait = t0 + req.due_s - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        rec = _record(req, req.due_s)
        rec["timed"] = 0.0 <= req.due_s < seconds
        th = threading.Thread(target=_consume, daemon=True,
                              args=(stream_fn, req, rec, t0, vocab))
        th.start()
        records.append(rec)
        threads.append(th)
    wait = t0 + seconds - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    on_window_end()
    _finish(records, threads, t0 + seconds + drain_s, t0)
    return records


def closed_loop(stream_fn, requests, *, clients: int, seconds: float,
                vocab: int, t0: float, on_window_end=lambda: None) -> list:
    """``clients`` callers, each sending its next request when the last
    returned, from before the window (``t0`` lies in the future: the
    lead-in) to its end. A request counts (is ``timed``) if it came
    back inside the window, whenever it was sent: the cell is judged on
    what the server delivered in the window, not on latency. Those in
    flight at the end are cut off uncounted."""
    records, lock, cursor = [], threading.Lock(), [0]
    end = t0 + seconds

    def client():
        while time.perf_counter() < end:
            with lock:
                req = requests[cursor[0] % len(requests)]
                cursor[0] += 1
                rec = _record(req, time.perf_counter() - t0)
                records.append(rec)
            _consume(stream_fn, req, rec, t0, vocab)
            rec["timed"] = 0.0 <= time.perf_counter() - t0 < seconds

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for th in threads:
        th.start()
    time.sleep(max(0.0, end - time.perf_counter()))
    on_window_end()
    return records
