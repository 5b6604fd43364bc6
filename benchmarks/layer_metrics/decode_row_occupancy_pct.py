"""Share of the decode program's rows that do work: `live_row_trips` (the
trips in which a row is live, summed over a chunk's rows) over `rows` x
`chunk` (the width the program ran at, times its trips), summed over the
`serving.decode` spans that lie wholly in the traced window. Below 100 the
chunk multiplies rows that carry nothing: clients waiting for admission,
rows that finished inside the chunk, an open loop below capacity. As an
earlier line, the mean rows in flight and the mean rows feeding prompt
tokens. None without a device trace or the stat `rows`."""
import json

from lib import spans


def compute(record, trace):
    t = spans.load(record)
    found = [sp for sp, _, _ in (spans.under(t, "serving.decode") if t else [])
             if "rows" in sp.stats and "live_row_trips" in sp.stats]
    width = sum(int(sp.stats["rows"]) * int(sp.stats["chunk"])
                for sp in found)
    if not width:
        return None

    def total(stat):
        return sum(int(sp.stats.get(stat, 0)) for sp in found)

    print(json.dumps({"decode_rows": {
        "chunks": len(found), "rows": total("rows") / len(found),
        "num_seqs": total("num_seqs") / len(found),
        "feeding_rows": total("feeding_rows") / len(found)}}), flush=True)
    return 100.0 * total("live_row_trips") / width
