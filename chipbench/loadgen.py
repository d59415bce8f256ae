#!/usr/bin/env python3
"""The load generator: a process of its own that never imports JAX.

Reads one traffic file, builds the plan from the seed
(``generators/<kind>.py``), drives the replica's JSONL wire over TCP on
its own clock, and prints what it saw: event lines while it runs
(``open``, ``close``) and one final document with a record per request
and the sample of finished greedy requests that the check compares.

    python3 chipbench/loadgen.py --port P --traffic FILE --seed N \
        --seconds S --vocab V

Arrivals: ``closed`` keeps a fixed number of requests outstanding (an
offline job's client); ``poisson`` starts sessions on a schedule whatever
the server does (independent users), and a later turn of a session is due
a think time after the previous answer arrived. Each request is timed
from when it was DUE, so a late generator shows as latency, and how late
it ran is reported.
"""

import argparse
import heapq
import importlib.util
import json
import os
import queue
import random
import socket
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TAIL_S = 60.0           # how long past the close an answer is waited for


def load_generator(kind: str):
    path = os.path.join(HERE, "generators", f"{kind}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_gen_{kind}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def say(doc: dict):
    print(json.dumps(doc), flush=True)


def drive(plan: dict, sock: socket.socket, seconds: float, check_sample: int,
          seed: int, now=time.monotonic, sleep=time.sleep) -> dict:
    sessions = plan["sessions"]
    arrival = plan["arrival"]
    inbox: "queue.Queue" = queue.Queue()

    def reader():
        try:
            with sock.makefile("r", encoding="utf-8") as f:
                for line in f:
                    inbox.put((now(), json.loads(line)))
        except (OSError, ValueError):
            pass
        inbox.put((now(), None))

    threading.Thread(target=reader, daemon=True, name="loadgen-read").start()
    records, prompts = [], {}
    outstanding = {}
    heap = []                       # (due, order, session, turn)
    order = 0
    t0 = now()
    t_open = t0 + plan["ramp_s"]
    t_close = t_open + seconds
    next_closed = 0                 # closed arrivals: next session to start

    def push(due, si, ti):
        nonlocal order
        heapq.heappush(heap, (due, order, si, ti))
        order += 1

    if arrival["kind"] == "poisson":
        for si, s in enumerate(sessions):
            if s["turns"]:
                push(t0 + s["arrive_s"], si, 0)
    else:
        while next_closed < min(int(arrival["outstanding"]), len(sessions)):
            push(t0, next_closed, 0)
            next_closed += 1
    history = {}                    # session -> tokens so far

    def send(due, si, ti):
        s = sessions[si]
        turn = s["turns"][ti]
        if ti == 0:
            base = list(plan["prefixes"][s["tenant"]]) \
                if s["tenant"] is not None else []
        else:
            base = history[si]
        prompt = base + turn["new"]
        rid = len(records)
        req = {"id": rid, "prompt": prompt, "max_new": turn["max_new"],
               "temperature": turn["temperature"], "top_k": turn["top_k"],
               "tenant": f"t{s['tenant']}" if s["tenant"] is not None
               else "default"}
        sent = now()
        sock.sendall((json.dumps(req) + "\n").encode("utf-8"))
        rec = {"id": rid, "session": si, "turn": ti, "due": due,
               "sent": sent, "arrive": None, "n_prompt": len(prompt),
               "n_out": 0, "greedy": turn["temperature"] == 0.0,
               "ttft_ms": None, "latency_ms": None, "error": None}
        records.append(rec)
        prompts[rid] = prompt
        outstanding[rid] = rec

    said_open = said_close = False
    served = {}                     # rid -> tokens (kept for the sample)
    dead = False
    while True:
        t = now()
        if not said_open and t >= t_open:
            say({"event": "open", "t": t, "wall": time.time()})
            said_open = True
        if not said_close and t >= t_close:
            say({"event": "close", "t": t, "wall": time.time()})
            said_close = True
        while heap and heap[0][0] <= t and t < t_close:
            due, _, si, ti = heapq.heappop(heap)
            send(due, si, ti)
        if t >= t_close and (not outstanding or dead
                             or t >= t_close + TAIL_S):
            break
        waits = [t_close + TAIL_S - t]
        if heap and t < t_close:
            waits.append(heap[0][0] - t)
        if not said_open:
            waits.append(t_open - t)
        if not said_close:
            waits.append(t_close - t)
        try:
            at, doc = inbox.get(timeout=max(0.0, min(waits)))
        except queue.Empty:
            continue
        if doc is None:
            dead = True             # the server hung up
            if not outstanding and not heap:
                break
            continue
        rec = outstanding.pop(doc.get("id"), None)
        if rec is None:
            continue
        rec["arrive"] = at
        if doc.get("error"):
            rec["error"] = str(doc["error"])
        else:
            rec["n_out"] = len(doc["tokens"])
            rec["ttft_ms"] = doc["ttft_ms"]
            rec["latency_ms"] = doc["latency_ms"]
            served[rec["id"]] = doc["tokens"]
        si, ti = rec["session"], rec["turn"]
        s = sessions[si]
        if not rec["error"] and ti + 1 < len(s["turns"]):
            history[si] = prompts[rec["id"]] + doc["tokens"]
            push(at + s["turns"][ti + 1]["think_s"], si, ti + 1)
        elif arrival["kind"] == "closed" and next_closed < len(sessions):
            push(at, next_closed, 0)
            next_closed += 1
        if rec["id"] not in served or not rec["greedy"]:
            prompts.pop(rec["id"], None)
    # the sample the check compares: finished greedy requests that were
    # served inside the window, the longest with them, drawn by the seed
    pool = [r for r in records
            if r["greedy"] and r["id"] in served and r["n_out"] > 0
            and r["arrive"] >= t_open and r["sent"] < t_close]
    pool.sort(key=lambda r: r["id"])
    sample = []
    if pool:
        longest = max(pool, key=lambda r: (r["n_prompt"] + r["n_out"],
                                           -r["id"]))
        rest = [r for r in pool if r is not longest]
        random.Random(int(seed) ^ 0x5EED).shuffle(rest)
        sample = [longest] + rest[:max(0, check_sample - 1)]
    late = [r["sent"] - r["due"] for r in records]
    return {
        "t0": t0, "t_open": t_open, "t_close": t_close,
        "records": records,
        "sample": [{"id": r["id"], "session": r["session"],
                    "turn": r["turn"], "prompt": prompts[r["id"]],
                    "tokens": served[r["id"]]} for r in sample],
        "unanswered": len(outstanding),
        "generator_late_ms": {"max": 1000 * max(late) if late else 0.0,
                              "mean": 1000 * sum(late) / len(late)
                              if late else 0.0}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    args = ap.parse_args(argv)
    if "jax" in sys.modules:
        raise SystemExit("loadgen: JAX got imported; this process must "
                         "not hold the chip")
    with open(args.traffic) as f:
        params = json.load(f)
    gen = load_generator(params["generator"])
    plan = gen.plan(params, args.seed, args.vocab, args.seconds)
    sock = socket.create_connection(("127.0.0.1", args.port), timeout=30)
    sock.settimeout(None)
    try:
        doc = drive(plan, sock, args.seconds,
                    int(params.get("check_sample", 6)), args.seed)
    finally:
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        sock.close()
    say({"event": "done", **doc})
    return 0


if __name__ == "__main__":
    sys.exit(main())
