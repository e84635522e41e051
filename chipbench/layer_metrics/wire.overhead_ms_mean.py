"""Mean client latency (due -> reply read) minus the server's own mean
request time (``ServingStats.request_ms``: enqueue -> result) over the
window: JSON parse, socket, reply write, and the client's side."""
LAYER = "wire"
UNIT = "ms"
MOVES = "serve.p95_ms"


def read(run):
    client = run.counts.get("client_ms_mean")
    server = run.counts.get("server_request_ms_mean")
    if client is None or server is None:
        return None
    return client - server
