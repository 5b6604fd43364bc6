"""Facts of a compiled program: what the compiler reserved on one device
and how many Mosaic kernels it holds (copied from chip_smoke.py
`program_facts`). On this runtime `memory_stats()["peak_bytes_in_use"]`
counts the arrays a process holds and not a running program's temporaries
(PERF.md section 6, PR 23), so a cell's peak is the larger of that and its
biggest program's reservation."""
from __future__ import annotations


def facts(compiled) -> tuple[str, dict]:
    """(compiled HLO text, {tpu_custom_calls, bytes by kind, total})."""
    text = compiled.as_text()
    m = compiled.memory_analysis()
    by_kind = {"arguments": m.argument_size_in_bytes,
               "outputs": m.output_size_in_bytes,
               "aliased": m.alias_size_in_bytes,
               "temporaries": m.temp_size_in_bytes}
    total = (by_kind["arguments"] + by_kind["temporaries"]
             + max(0, by_kind["outputs"] - by_kind["aliased"]))
    return text, {"tpu_custom_calls": text.count("tpu_custom_call"),
                  "program_bytes": by_kind, "program_total_bytes": total}
