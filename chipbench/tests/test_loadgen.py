"""The open-loop generator's due-time arithmetic, against a server that
stalls: the requests behind the stall carry its wait."""

import json
import socket
import threading
import time

import numpy as np

from chipbench import loadgen


def stalling_server(stall_at, stall_s):
    """Answers each line in order; sleeps before answering line ``stall_at``."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)

    def serve():
        conn, _ = srv.accept()
        with conn, conn.makefile("rwb") as f:
            for i, line in enumerate(f):
                if i == stall_at:
                    time.sleep(stall_s)
                f.write(json.dumps({"score": float(i)}).encode() + b"\n")
                f.flush()
        srv.close()

    threading.Thread(target=serve, daemon=True).start()
    return srv.getsockname()[1]


def test_a_stall_lengthens_the_latency_of_later_requests():
    port = stalling_server(stall_at=2, stall_s=0.4)
    due = np.arange(8) * 0.05  # one every 50 ms
    lines = [b"{}\n"] * 8
    _, sent, done, replies = loadgen.drive(port, lines, due, connections=1)
    lat = loadgen.latencies(due, done)
    assert [json.loads(r)["score"] for r in replies] == [
        float(i) for i in range(8)]
    assert np.all(sent - due < 0.03)           # the generator itself kept time
    assert lat[0] < 0.05 and lat[1] < 0.05      # before the stall
    assert lat[2] >= 0.4                        # the stalled request
    # request 3 was due 50 ms after request 2, behind a 400 ms stall
    assert 0.3 <= lat[3] < 0.45
    assert lat[3] > lat[4] > lat[5]             # the queue drains


def test_every_seed_offers_the_same_load_in_another_order():
    t = {"rate_per_s": 200, "lead_in_s": 1.0, "arrivals": "poisson",
         "schedule_seed": 1, "num_users": 1 << 16, "user_zipf_exponent": 1.1}
    d1, u1, lead1 = loadgen.schedule(t, seed=5, seconds=4.0)
    d2, u2, lead2 = loadgen.schedule(t, seed=2**31 + 77, seconds=4.0)
    assert d1.size == d2.size == 1000
    assert not np.array_equal(d1, d2)
    g1, g2 = np.diff(d1), np.diff(d2)
    assert np.isclose(d1[-1] + (5.0 - d1[-1]), 5.0)
    assert np.allclose(np.sort(u1), np.sort(u2))          # same users
    assert abs(np.sort(g1).sum() - np.sort(g2).sum()) < 0.1  # same gaps
    assert abs(lead1 - lead2) <= 40
