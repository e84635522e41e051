"""``peak_bytes_in_use`` of the fullest device over its ``bytes_limit``, read
after the window and before the reference runs."""
LAYER = "device"
UNIT = "%"
MOVES = "setup_s"


def read(run):
    peak = run.counts.get("memory_peak_bytes")
    limit = run.counts.get("memory_limit_bytes")
    if not peak or not limit:
        return None
    return 100.0 * peak / limit
