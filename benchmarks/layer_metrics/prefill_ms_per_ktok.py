"""Host milliseconds of dense prefill a thousand prompt tokens: host time
of the `serving.prefill` spans that lie wholly in the traced window over
the sum of their `tokens`. Lengths differ from prompt to prompt and from
cell to cell; a rate per token is what can be followed from PR to PR.
None without a device trace or such a span."""
from lib import spans


def compute(record, trace):
    t = spans.load(record)
    found = [(sp, host) for sp, host, _ in
             (spans.under(t, "serving.prefill") if t else [])
             if int(sp.stats.get("tokens", 0))]
    if not found:
        return None
    return 1e-3 * sum(host for _, host in found) \
        / sum(int(sp.stats["tokens"]) for sp, _ in found)
