"""Host wall of the INDEX_MAP compaction at set-up: the summed durations of
the program's ``game.index_map`` spans, one a sparse random effect (the
active rows' (lane, column) pairs sorted into unions, the buckets' widths,
every entry's local id, every row's flat table positions, the compact
arrays put on the device).  Nothing on a checkout without the span."""
LAYER = "random-effect design"
UNIT = "s"
MOVES = "setup_s"


def read(run):
    spans = run.counts.get("index_map_spans") or []
    if not spans:
        return None
    return sum(seconds for seconds, _ in spans)
