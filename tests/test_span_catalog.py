"""The program's spans in the profiler's own trace (PR 27).

One switch: a profiler session opened by any caller
(`jax.profiler.start_trace`) shows the catalog of docs/observability.md
without `obs.trace.enable()`, which keeps its one use, the in-process
table. The names, the nesting and the stats are the contract with the
benchmark's readers (benchmarks/lib/spans.py)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.inference.serving import (EngineConfig, LLMEngine,
                                          SamplingParams)
from paddle_tpu.models.gpt import GPT, GPTConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: span -> the span it lies in (docs/observability.md, "Span catalog")
CATALOG = {
    "serving.add_request": None,
    "serving.engine_step": None,
    "serving.schedule": "serving.engine_step",
    "serving.prefill": "serving.engine_step",
    "serving.prefill.forward": "serving.prefill",
    "serving.prefill.write_cache": "serving.prefill",
    "serving.prefill.fetch": "serving.prefill",
    "serving.prefill.sample": "serving.prefill",
    "serving.decode": "serving.engine_step",
    "serving.decode.pack": "serving.decode",
    "serving.decode.dispatch": "serving.decode",
    "serving.decode.fetch": "serving.decode",
    "serving.decode.drain": "serving.decode",
}


def _session(trace_dir, work):
    """Run `work` under a profiler session; [(name, start, end, stats)] of
    the host plane's events whose name has a dot (the program's spans)."""
    from jax.profiler import ProfileData, ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    found = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(found) == 1
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(found[0]).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(("serving.", "test."))]


@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    paddle.seed(0)
    model = GPT(GPTConfig(vocab_size=89, hidden_size=32, num_layers=2,
                          num_heads=4, max_seq_len=24))
    model.eval()
    eng = LLMEngine.from_model(model, EngineConfig(
        block_size=4, num_blocks=16, max_num_seqs=4))
    assert eng.config.decode_chunk_size == 8

    def work():
        eng.add_request(np.arange(1, 6, dtype=np.int32),
                        SamplingParams(max_tokens=6), request_id="five")
        eng.add_request(np.arange(7, 10, dtype=np.int32),
                        SamplingParams(max_tokens=12), request_id="three")
        eng.step()              # both prefills, each emits its first token
        eng.step()              # one chunk: both rows decode

    assert not obs.trace.is_enabled()
    before = len(obs.trace.events())
    spans = _session(tmp_path_factory.mktemp("engine_trace"), work)
    assert len(obs.trace.events()) == before    # the table stayed shut
    return spans


@pytest.mark.parametrize("name", sorted(CATALOG))
def test_every_span_of_the_catalog_is_in_the_profilers_trace(
        engine_trace, name):
    mine = [s for s in engine_trace if s[0] == name]
    assert mine, f"{name} is not in the trace"
    parent = CATALOG[name]
    if parent is not None:
        around = [s for s in engine_trace if s[0] == parent]
        for _, start, end, _ in mine:
            assert any(a <= start and end <= b for _, a, b, _ in around), \
                f"{name} lies outside every {parent}"


def test_the_catalog_is_all_the_engine_emits(engine_trace):
    assert {s[0] for s in engine_trace} == set(CATALOG)


def test_spans_of_one_request_share_its_id(engine_trace):
    added = {s[3]["request_id"]: s for s in engine_trace
             if s[0] == "serving.add_request"}
    prefilled = {s[3]["request_id"]: s for s in engine_trace
                 if s[0] == "serving.prefill"}
    assert set(added) == set(prefilled) == {"five", "three"}
    assert added["five"][3]["prompt_tokens"] == 5
    assert prefilled["three"][3]["tokens"] == 3
    for rid in added:           # a request is added before it is prefilled
        assert added[rid][2] <= prefilled[rid][1]
    blocks = sorted(s[3]["blocks"] for s in engine_trace
                    if s[0] == "serving.prefill.write_cache")
    assert blocks == [1, 2]     # ceil(3 / 4) and ceil(5 / 4) blocks of 4


def test_decode_span_counts_the_positions_its_rows_attend_to(engine_trace):
    (decode,) = [s for s in engine_trace if s[0] == "serving.decode"]
    # "five": 5 prompt positions, 1 of 6 tokens out: 5 trips reading
    # 6..10 positions; "three": 3 and 1 of 12: all 8 trips, 4..11
    by_hand = sum(range(6, 11)) + sum(range(4, 12))
    # and the live rows summed over the trips: 5 + 8
    # both requests are greedy: no row of the chunk samples
    # PR 37: the program's row width (`max_num_seqs` under the ragged
    # kernel) and the rows that fed prompt tokens, from inside the scope
    # PR 39: the table entries those positions lie in, blocks of 4:
    # ceil(6/4) .. ceil(10/4) and ceil(4/4) .. ceil(11/4)
    blocks_by_hand = sum(-(-n // 4) for n in (*range(6, 11), *range(4, 12)))
    assert decode[3] == {"num_seqs": 2, "chunk": 8,
                         "context_tokens": by_hand, "live_row_trips": 13,
                         "live_blocks": blocks_by_hand,
                         "sampled_rows": 0, "rows": 4, "feeding_rows": 0}
    steps = [s[3]["step"] for s in engine_trace
             if s[0] == "serving.engine_step"]
    assert steps == [1, 2]


def _chunk_row(pf_target, prefill_pos, out, max_tokens, pos):
    from paddle_tpu.inference.serving.scheduler import Request
    r = Request("r", np.zeros(pf_target or 1, np.int32),
                SamplingParams(max_tokens=max_tokens))
    r.pf_target, r.prefill_pos = pf_target, prefill_pos
    r.output_ids, r.slot = [0] * out, (0, 0, pos)
    return r


@pytest.mark.parametrize("block_size", [1, 4, 32])
@pytest.mark.parametrize("row", [
    (32, 12, 0, 4, 12),     # all 8 trips eat prompt
    (32, 29, 0, 2, 29),     # 3 prompt trips, one decode trip
    (0, 0, 4, 5, 9),        # plain decode, one token left
    (0, 0, 0, 64, 31),      # a block boundary inside the chunk
    (0, 0, 0, 64, 0),       # from the first position
], ids=["feeding", "feed-then-decode", "one-trip", "boundary", "start"])
def test_live_blocks_are_the_table_entries_the_positions_lie_in(row,
                                                                block_size):
    from paddle_tpu.inference.serving.engine import (_live_blocks,
                                                     _live_trips)
    req = _chunk_row(*row)
    by_hand = sum(-(-(req.slot[2] + j + 1) // block_size)
                  for j in range(_live_trips(req, 8)))
    assert _live_blocks([req, req], 8, block_size) == 2 * by_hand


def test_context_tokens_of_a_row_still_in_chunked_prefill():
    from paddle_tpu.inference.serving.engine import _context_tokens
    row = _chunk_row
    # 20 prompt tokens left at position 12: all 8 trips eat prompt
    assert _context_tokens([row(32, 12, 0, 4, 12)], 8) == sum(range(13, 21))
    # 3 left at 29, 2 tokens wanted: trips at 29, 30, 31 (the third
    # samples), then one decode trip at 32
    assert _context_tokens([row(32, 29, 0, 2, 29)], 8) == sum(range(30, 34))
    # plain decode, one token left of 5 at position 9
    assert _context_tokens([row(0, 0, 4, 5, 9)], 8) == 10


def test_args_become_stats_and_annotate_false_stays_out(tmp_path):
    def work():
        with obs.span("test.with_args",
                      args={"n": 3, "who": "a", "f": 0.5, "many": [1, 2]}):
            with obs.Span("test.silent", annotate=False):
                pass

    spans = _session(tmp_path, work)
    assert [s[0] for s in spans] == ["test.with_args"]
    # scalars arrive as stats on the clean name, a list does not
    assert spans[0][3] == {"n": 3, "who": "a", "f": 0.5}


def test_enable_fills_the_table_and_nothing_else_is_needed_for_it():
    obs.trace.enable()
    try:
        with obs.span("test.tabled", cat="train", args={"k": 1}):
            pass
    finally:
        obs.trace.disable()
    (ev,) = [e for e in obs.trace.events() if e.name == "test.tabled"]
    assert ev.cat == "train" and ev.args == {"k": 1}


def test_obs_and_its_spans_leave_jax_unimported():
    """The offline tools' import path (tools/reqtrace.py): the obs package
    alone, in a fresh process."""
    code = (
        "import sys; sys.path.insert(0, %r); import obs\n"
        "with obs.span('x', args={'a': 1}): pass\n"
        "with obs.Span('y', annotate=False): pass\n"
        "obs.trace.enable()\n"
        "with obs.span('z'): pass\n"
        "assert [e.name for e in obs.trace.events()] == ['z']\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n"
        % os.path.join(ROOT, "paddle_tpu"))
    done = subprocess.run([sys.executable, "-c", code], cwd="/",
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


def test_train_step_holds_its_metric_children_and_feeds_them():
    paddle.seed(0)
    model = paddle.nn.Linear(4, 2)
    optim = paddle.optimizer.SGD(0.1, parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, lambda m, x, y: ((m(x) - y) ** 2).mean(), optim)
    held = (step._obs_step_seconds, step._obs_tokens,
            step._obs_tokens_per_sec)
    x = paddle.to_tensor(np.random.randn(8, 4).astype("float32"))
    y = paddle.to_tensor(np.random.randn(8, 2).astype("float32"))
    before = step._obs_step_seconds.count
    for _ in range(3):
        step(x, y)              # the first dispatch only arms the clock
    assert step._obs_step_seconds.count == before + 2
    assert held == (step._obs_step_seconds, step._obs_tokens,
                    step._obs_tokens_per_sec)
    assert obs.REGISTRY.get("train_step_seconds").labels() is held[0]


def test_the_static_roofline_gauges_are_gone():
    for name in ("set_" + "roofline", "get_" + "roofline"):
        assert not hasattr(obs, name)
    names = {f.name for f in obs.REGISTRY.families()}
    assert not {n for n in names if "roofline" in n}
