"""Requests left waiting after a step's admission: the mean of `waiting`
over the `serving.schedule` spans that lie wholly in the traced window. As
an earlier line, what ended admission (`held_by`: the queue ran empty,
every row taken, the prefill budget, the watermark, the window group, the
blocks) as shares of the steps, the mean requests and prompt tokens a
step admitted, and the least and mean `free_blocks` after admission (how
far the pool was from holding the next prompt where a step was held by
the watermark or the blocks). None without a device trace or the stat
`waiting`."""
import collections
import json

from lib import spans


def compute(record, trace):
    t = spans.load(record)
    found = [sp for sp, _, _ in
             (spans.under(t, "serving.schedule") if t else [])
             if "waiting" in sp.stats]
    if not found:
        return None

    def mean(stat):
        return sum(int(sp.stats.get(stat, 0)) for sp in found) / len(found)

    held = collections.Counter(str(sp.stats.get("held_by")) for sp in found)
    print(json.dumps({"schedule": {
        "steps": len(found),
        "held_by": {k: v / len(found) for k, v in sorted(held.items())},
        "prefill": mean("prefill"), "prefill_tokens": mean("prefill_tokens"),
        "chunked": mean("chunked"), "decode": mean("decode"),
        "preempted": mean("preempted"),
        "free_blocks": mean("free_blocks"),
        "free_blocks_least": min(int(sp.stats.get("free_blocks", 0))
                                 for sp in found)}}), flush=True)
    return mean("waiting")
