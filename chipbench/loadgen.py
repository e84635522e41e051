"""The open-loop load generator.  It runs as a process of its own (``python3
-m chipbench.loadgen``), so that the clients share no interpreter lock with
the server they measure, and it never imports JAX: the chip stays with the
parent.

One traffic file drives it (``kind: serve_open_loop``): ``rate_per_s``, the
arrival law, the entity skew, the connections and the unmeasured lead-in.
The *multiset* of inter-arrival gaps and of user ranks is drawn from the
traffic file's own ``schedule_seed``; ``--seed`` permutes them and draws the
feature values, so every seed offers the same load in another order.

Every request is timed from the moment it was *due*, not from the moment it
left: a stalled server lengthens the latency of the requests behind the
stall (see ``latencies``).

Protocol with the parent: it writes one JSON spec line to our stdin; we build
every payload, print ``READY``; on ``GO`` we send the lead-in and then the
measured requests, print ``WINDOW`` when the measured part starts, and at the
end one JSON line with the per-request arrays.
"""

from __future__ import annotations

import json
import selectors
import socket
import sys
import time

import numpy as np


def schedule(traffic: dict, seed: int, seconds: float):
    """Due times (s from the start of sending) of the lead-in and the
    measured requests, and the user rank of each.  Returns (due (n,), users
    (n,), n_lead): the first ``n_lead`` requests are unmeasured."""
    rate = float(traffic["rate_per_s"])
    lead = float(traffic["lead_in_s"])
    n = int(round(rate * (lead + seconds)))
    base = np.random.default_rng([int(traffic["schedule_seed"]), n])
    if traffic["arrivals"] == "poisson":
        gaps = base.exponential(1.0 / rate, size=n)
    elif traffic["arrivals"] == "uniform":
        gaps = np.full(n, 1.0 / rate)
    else:
        raise ValueError(f"unknown arrival law {traffic['arrivals']!r}")
    gaps *= (lead + seconds) / gaps.sum()  # the same offered rate every time
    users = zipf_ranks(
        base.uniform(size=n), int(traffic["num_users"]),
        float(traffic["user_zipf_exponent"]),
    )
    order = np.random.default_rng([int(seed), 1]).permutation(n)
    due = np.cumsum(gaps[order])
    due -= gaps[order][0]
    return due, users[order], int(np.searchsorted(due, lead))


def zipf_ranks(u, n_items: int, exponent: float):
    a = 1.0 - exponent
    r = ((float(n_items) ** a - 1.0) * u + 1.0) ** (1.0 / a)
    return np.clip(r.astype(np.int64) - 1, 0, n_items - 1)


def relabel(ranks, seed: int, n_items: int):
    """The seed's bijection of user ranks onto table rows (n_items = 2**k)."""
    mult = int(np.random.default_rng([int(seed), 0x0DD]).integers(
        1 << 20, 1 << 31)) * 2 + 1
    return (ranks.astype(np.uint64) * np.uint64(mult)) % np.uint64(n_items)


def features(seed: int, n: int, d_fixed: int, d_user: int):
    """(xg (n, d_fixed), xu (n, d_user)) float32 feature values."""
    rng = np.random.default_rng([int(seed), 2])
    return (
        rng.standard_normal((n, d_fixed), dtype=np.float32),
        rng.standard_normal((n, d_user), dtype=np.float32),
    )


def requests(traffic: dict, seed: int, seconds: float, d_fixed: int,
             d_user: int):
    """Everything one run sends: due, user ids, features, lead-in count."""
    due, ranks, n_lead = schedule(traffic, seed, seconds)
    users = relabel(ranks, seed, int(traffic["num_users"])).astype(np.int64)
    xg, xu = features(seed, due.size, d_fixed, d_user)
    return due, users, xg, xu, n_lead


def payloads(users, xg, xu):
    """The JSON lines of ``cli/serve.py``'s protocol, pre-built as bytes."""
    gk = [f"g{j}" for j in range(xg.shape[1])]
    uk = [f"u{j}" for j in range(xu.shape[1])]
    out = []
    for i in range(users.size):
        feats = dict(zip(gk, xg[i].tolist()))
        feats.update(zip(uk, xu[i].tolist()))
        out.append(
            (json.dumps({"features": feats,
                         "entities": {"userId": int(users[i])}}) + "\n"
             ).encode()
        )
    return out


def latencies(due, done):
    """Latency of each request from its DUE time.  ``done`` is when its reply
    was read (nan = never).  A request that could only leave late, because
    the generator or the connection was held up, carries that wait."""
    return np.asarray(done, float) - np.asarray(due, float)


def drive(port: int, lines, due, connections: int, drain_s: float = 60.0,
          mark_at: int = -1, on_mark=None):
    """Send ``lines[i]`` at ``t0 + due[i]`` over ``connections`` sockets
    (request i on connection i % connections), read the replies.  One thread:
    a selector loop that sends what is due and reads what has come, and calls
    ``on_mark()`` just before request ``mark_at`` leaves.  Returns
    (t0, sent (n,), done (n,), replies list)."""
    n = len(lines)
    socks, bufs, queues = [], [], []
    sel = selectors.DefaultSelector()
    for c in range(connections):
        s = socket.create_connection(("127.0.0.1", port))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sel.register(s, selectors.EVENT_READ, c)
        socks.append(s)
        bufs.append(b"")
        queues.append([])  # request ids awaiting a reply, in order
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    replies = [None] * n
    nxt, answered = 0, 0
    t0 = time.perf_counter()
    deadline = None
    try:
        while answered < n:
            now = time.perf_counter() - t0
            while nxt < n and due[nxt] <= now:
                if nxt == mark_at and on_mark is not None:
                    on_mark()
                c = nxt % connections
                socks[c].sendall(lines[nxt])
                sent[nxt] = time.perf_counter() - t0
                queues[c].append(nxt)
                nxt += 1
                now = time.perf_counter() - t0
            if nxt < n:
                timeout = max(due[nxt] - now, 0.0)
            else:
                if deadline is None:
                    deadline = now + drain_s
                timeout = min(0.05, max(deadline - now, 0.0))
                if now >= deadline:
                    break
            for key, _ in sel.select(timeout):
                c = key.data
                data = key.fileobj.recv(1 << 16)
                t = time.perf_counter() - t0
                if not data:
                    sel.unregister(key.fileobj)
                    continue
                bufs[c] += data
                while b"\n" in bufs[c]:
                    line, bufs[c] = bufs[c].split(b"\n", 1)
                    i = queues[c].pop(0)
                    done[i] = t
                    replies[i] = line.decode()
                    answered += 1
    finally:
        for s in socks:
            s.close()
        sel.close()
    return t0, sent, done, replies


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    traffic, seed = spec["traffic"], spec["seed"]
    due, users, xg, xu, n_lead = requests(
        traffic, seed, spec["seconds"], spec["d_fixed"], spec["d_user"]
    )
    lines = payloads(users, xg, xu)
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    # the lead-in and the window are one stream; WINDOW marks the boundary
    _, sent, done, replies = drive(
        spec["port"], lines, due, int(traffic["connections"]),
        mark_at=n_lead, on_mark=lambda: print("WINDOW", flush=True),
    )
    scores, errors = [], 0
    for r in replies[n_lead:]:
        try:
            scores.append(float(json.loads(r)["score"]))
        except (TypeError, ValueError, KeyError):
            scores.append(None)
            errors += r is not None
    out = {
        "n_lead": n_lead,
        "due": due[n_lead:].tolist(),
        "sent": [None if x != x else x for x in sent[n_lead:].tolist()],
        "done": [None if x != x else x for x in done[n_lead:].tolist()],
        "scores": scores,
        "error_replies": errors,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
