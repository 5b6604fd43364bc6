"""Tier-1 tests for the jaxcost static cost model + budget gate.

Four layers:

  1. cost fixtures    — hand-computed FLOPs/bytes/peak/comm on crafted
                        jaxprs (matmul chain, scan carry, psum tree,
                        cond branches) asserted EXACTLY;
  2. donation audit   — a toy true positive, the BatchNorm-buffers
                        catch that motivated TrainStep's donate set,
                        and the registry's zero-unsuppressed gate;
  3. donation safety  — donated vs undonated TrainStep twins produce
                        bitwise-identical losses and parameters;
  4. budget gate      — tools/jaxcost.py --budget check passes on the
                        committed jaxcost_budget.json and exits nonzero
                        when a budget is exceeded past tolerance.

Also pins the hlo_bytes single-source contract: tools/hlo_bytes.py is a
wrapper with no byte-accounting logic of its own.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.analysis import hlo_bytes as hb
from paddle_tpu.analysis import jaxcost
from paddle_tpu.analysis.liveness import peak_live_bytes

pytestmark = pytest.mark.lint

REPO = pathlib.Path(__file__).resolve().parent.parent
JAXCOST_CLI = REPO / "tools" / "jaxcost.py"
BUDGET_FILE = REPO / "jaxcost_budget.json"

F32 = jnp.float32


# ---------------------------------------------------------- cost fixtures
def test_matmul_chain_exact():
    """(a@b)@c with a[8,16] b[16,32] c[32,4] f32.

    flops: 2*8*32*16 + 2*8*4*32 = 8192 + 2048 = 10240
    read:  a 512 + b 2048 + ab 1024 + c 512   = 4096
    write: ab 1024 + out 128                  = 1152
    peak:  entry 3072 live + ab 1024          = 4096
    """
    a = jnp.zeros((8, 16), F32)
    b = jnp.zeros((16, 32), F32)
    c = jnp.zeros((32, 4), F32)
    cost = jaxcost.estimate_fn(lambda a, b, c: jnp.dot(jnp.dot(a, b), c),
                               a, b, c, name="chain")
    assert cost.flops == 10240
    assert cost.bytes_read == 4096
    assert cost.bytes_written == 1152
    assert cost.peak_bytes == 4096
    assert cost.comm_bytes == 0
    assert cost.by_primitive["dot_general"]["count"] == 2


def test_scan_carry_exact():
    """scan of carry[4,4] @ W over length 5, stacking ys.

    flops: 2*4*4*4 per trip * 5      = 640
    read:  (carry 64 + W 64) * 5     = 640
    write: new-carry 64 * 5          = 320
    peak:  entry (c0+W) 128 + scan outs (carry 64 + ys 320)
           + body extra 64           = 576
    """
    W = jnp.zeros((4, 4), F32)

    def body(carry, _):
        new = jnp.dot(carry, W)
        return new, new

    def prog(c0):
        return jax.lax.scan(body, c0, None, length=5)

    cost = jaxcost.estimate_fn(prog, jnp.zeros((4, 4), F32), name="scan")
    assert cost.flops == 640
    assert cost.bytes_read == 640
    assert cost.bytes_written == 320
    assert cost.peak_bytes == 576
    assert cost.by_primitive["dot_general"]["count"] == 5  # dynamic count


def test_psum_tree_comm_exact():
    """Grad-sync shape: per-leaf psum over a 4-device dp axis under
    shard_map. Per-device shards: w [2,8]=64 B, b [1]=4 B; psum moves
    2x input bytes (reduce-scatter + all-gather) -> 2*68 = 136."""
    from paddle_tpu.parallel import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    assert len(devs) >= 4, "conftest forces an 8-device host platform"
    mesh = Mesh(np.asarray(devs[:4]), ("dp",))
    tree = {"w": jnp.zeros((8, 8), F32), "b": jnp.zeros((4,), F32)}

    def psum_tree(g):
        return jax.tree_util.tree_map(lambda x: jax.lax.psum(x, "dp"), g)

    pt = shard_map(psum_tree, mesh=mesh,
                   in_specs=({"w": P("dp", None), "b": P("dp")},),
                   out_specs={"w": P(None, None), "b": P(None)},
                   check_vma=False)
    cost = jaxcost.estimate_fn(pt, tree, name="pt")
    assert cost.flops == 0
    assert cost.comm_bytes == 136
    assert cost.peak_bytes == 404


def test_liveness_releases_dead_values():
    """x[256]f32 -> t=x+1 -> u=t*2: x dies after the first eqn, so both
    eqns peak at 2048 (one live input + one output), never 3072."""
    def prog(x):
        t = x + 1.0
        return t * 2.0

    rep = peak_live_bytes(jax.make_jaxpr(prog)(jnp.zeros((256,), F32)))
    assert rep.peak_bytes == 2048


def test_cond_charges_heaviest_branch():
    """cond(v@v, v+1) on [8,8]: flops = max(1024, 64) = 1024."""
    def prog(pred, x):
        return jax.lax.cond(pred, lambda v: jnp.dot(v, v),
                            lambda v: v + 1.0, x)

    cost = jaxcost.estimate_fn(prog, jnp.asarray(True),
                               jnp.zeros((8, 8), F32), name="cond")
    assert cost.flops == 1024


# --------------------------------------------------------- donation audit
def _toy_step(params, x):
    new = {k: v - 0.1 * v for k, v in params.items()}
    return new, (x * 2).sum()


def _toy_args():
    return ({"w": jnp.zeros((16, 16), F32), "b": jnp.zeros((16,), F32)},
            jnp.zeros((8,), F32))


def test_donation_audit_flags_undonated_params():
    params, x = _toy_args()
    findings = jaxcost.audit_donation(_toy_step, params, x, name="toy")
    assert [(f.argnum, f.nbytes, f.n_leaves) for f in findings] == \
        [(0, 1088, 2)]  # w 1024 + b 64, both aval-matched to outputs
    assert not findings[0].suppressed


def test_donation_audit_clean_when_donated():
    params, x = _toy_args()
    assert jaxcost.audit_donation(_toy_step, params, x, name="toy",
                                  donate_argnums=(0,)) == []


def test_donation_audit_suppression_keeps_finding_marked():
    params, x = _toy_args()
    findings = jaxcost.audit_donation(_toy_step, params, x, name="toy",
                                      suppress={0: "kept for rollback"})
    assert len(findings) == 1
    assert findings[0].suppressed == "kept for rollback"


def _bn_step():
    """The model that motivated TrainStep's donate set: BatchNorm
    carries running-stat BUFFERS (argnum 2), updated and returned every
    step — donatable, and invisible on buffer-less models."""
    import paddle_tpu as paddle
    paddle.seed(0)
    model = paddle.nn.Sequential(paddle.nn.Linear(16, 512),
                                 paddle.nn.BatchNorm1D(512))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())

    def loss_fn(m, x, y):
        d = m(x) - y
        return (d * d).mean()

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    x = paddle.to_tensor(np.zeros((4, 16), np.float32))
    y = paddle.to_tensor(np.zeros((4, 512), np.float32))
    return step, x, y


def test_trainstep_donates_buffers_the_old_set_missed():
    from paddle_tpu.analysis.jaxpr_audit import train_step_args
    step, x, y = _bn_step()
    args = train_step_args(step, x, y)
    # the pre-fix donate set (params/opt_state/rng_ctr, no buffers)
    old = jaxcost.audit_donation(step._raw_step, *args, name="bn",
                                 donate_argnums=(0, 3, 6))
    assert [(f.argnum, f.nbytes) for f in old] == [(2, 4096)]
    # the shipped set covers the running stats
    assert 2 in step._donate_argnums
    assert jaxcost.audit_donation(step._raw_step, *args, name="bn",
                                  donate_argnums=step._donate_argnums) \
        == []


def test_registry_has_zero_unsuppressed_findings():
    """ISSUE acceptance: after the TrainStep/_cache_write donation fix,
    the whole registry audits clean; the one intentional non-donation
    (serving pools, crash recovery) stays visible as suppressed."""
    findings = jaxcost.collect_donation_findings()
    unsuppressed = [f for f in findings if not f.suppressed]
    assert unsuppressed == [], "\n".join(f.format() for f in unsuppressed)
    assert any(f.program == "serving.paged_decode" and f.suppressed
               for f in findings)


def test_registry_names_cover_required_programs():
    names = set(jaxcost.registry_names())
    assert "train_step" in names
    assert {"decode.token_embed", "decode.qkv", "decode.cache_write",
            "decode.attn", "decode.head"} <= names
    assert {"serving.prefill", "serving.paged_decode"} <= names


# ------------------------------------------------ donation bitwise safety
def _twin(donate: bool):
    import paddle_tpu as paddle
    paddle.seed(0)
    model = paddle.nn.Sequential(paddle.nn.Linear(8, 16),
                                 paddle.nn.ReLU(),
                                 paddle.nn.Linear(16, 8))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())

    def loss_fn(m, x, y):
        d = m(x) - y
        return (d * d).mean()

    step = paddle.jit.TrainStep(model, loss_fn, opt, donate=donate)
    return model, step


def test_donation_is_bitwise_equivalent():
    """Donation is an aliasing hint, not a numerical change: 3 steps of
    seeded twin models must match bitwise in every loss and parameter."""
    import paddle_tpu as paddle
    rng = np.random.RandomState(0)
    batches = [(rng.randn(4, 8).astype(np.float32),
                rng.randn(4, 8).astype(np.float32)) for _ in range(3)]
    runs = {}
    for donate in (True, False):
        model, step = _twin(donate)
        losses = []
        for bx, by in batches:
            out = step(paddle.to_tensor(bx), paddle.to_tensor(by))
            losses.append(np.asarray(out.numpy()
                                     if hasattr(out, "numpy") else out))
        runs[donate] = (losses,
                        [np.asarray(p._value) for p in model.parameters()])
    for ld, lu in zip(*[runs[k][0] for k in (True, False)]):
        assert np.array_equal(ld, lu)
    for pd, pu in zip(*[runs[k][1] for k in (True, False)]):
        assert np.array_equal(pd, pu)


# ------------------------------------------------------------ budget gate
def _cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(JAXCOST_CLI), *args],
        capture_output=True, text=True, cwd=str(REPO), env=env,
        timeout=600)


def test_budget_check_passes_on_committed_file():
    """ISSUE acceptance: the committed jaxcost_budget.json covers every
    registry program and the full check (costs + donation audit) is
    green."""
    p = _cli("--budget", "check", "--format", "json")
    assert p.returncode == 0, p.stdout + p.stderr
    d = json.loads(p.stdout)
    assert d["budget_violations"] == []
    assert set(d["programs"]) == set(jaxcost.registry_names())
    assert all(not f["suppressed"] or
               f["program"] == "serving.paged_decode"
               for f in d["donation_findings"])


def test_budget_check_fails_when_peak_bytes_regress(tmp_path):
    """ISSUE acceptance: shrink train_step's peak-bytes budget by 1.2x
    (i.e. the current program exceeds it by ~20% > 5% tolerance) ->
    exit 1 naming the program and metric."""
    payload = json.loads(BUDGET_FILE.read_text())
    payload["programs"]["train_step"]["peak_bytes"] = int(
        payload["programs"]["train_step"]["peak_bytes"] / 1.2)
    f = tmp_path / "budget.json"
    f.write_text(json.dumps(payload))
    p = _cli("--budget", "check", "--budget-file", str(f),
             "--programs", "train_step", "--no-donation-audit")
    assert p.returncode == 1, p.stdout + p.stderr
    assert "BUDGET VIOLATION" in p.stdout
    assert "train_step" in p.stdout and "peak_bytes" in p.stdout


def test_budget_check_tolerates_small_drift(tmp_path):
    """A 4% overshoot sits inside the 5% tolerance -> exit 0."""
    payload = json.loads(BUDGET_FILE.read_text())
    payload["programs"]["train_step"]["peak_bytes"] = int(
        payload["programs"]["train_step"]["peak_bytes"] / 1.04)
    f = tmp_path / "budget.json"
    f.write_text(json.dumps(payload))
    p = _cli("--budget", "check", "--budget-file", str(f),
             "--programs", "train_step", "--no-donation-audit")
    assert p.returncode == 0, p.stdout + p.stderr


def test_cli_rejects_unknown_program():
    p = _cli("--programs", "no_such_program", "--no-donation-audit")
    assert p.returncode == 2
    assert "unknown program" in p.stderr


# ------------------------------------------------- hlo_bytes single source
def test_hlo_bytes_tool_is_a_thin_wrapper():
    """tools/hlo_bytes.py must carry no byte-accounting logic of its
    own — one dtype table, one parser, in analysis/hlo_bytes.py."""
    src = (REPO / "tools" / "hlo_bytes.py").read_text()
    assert "analysis.hlo_bytes" in src
    assert "def shape_bytes" not in src
    assert "def audit_text" not in src
    assert "_DTYPE_BYTES" not in src


def test_hlo_shape_bytes_and_allreduce_payload():
    assert hb.shape_bytes("f32[8,2]") == 64
    assert hb.shape_bytes("(f32[8]{0}, bf16[4,4])") == 64
    hlo = ("  %ar = f32[1024]{0} all-reduce(%p0), replica_groups={}\n"
           "  %ar2 = (f32[8]{0}, f32[16]{0}) all-reduce(%a, %b)\n"
           "  %use = f32[1024]{0} add(%ar, %ar)\n")
    assert hb.allreduce_payload(hlo) == (4096 + 32 + 64, 2)


def test_hlo_bytes_cli_runs(tmp_path):
    hlo = ("HloModule m\n\n"
           "ENTRY main {\n"
           "  %p0 = f32[8,16]{1,0} parameter(0)\n"
           "  %e = f32[8,16]{1,0} exponential(%p0)\n"
           "}\n")
    f = tmp_path / "dump.txt"
    f.write_text(hlo)
    p = subprocess.run(
        [sys.executable, str(REPO / "tools" / "hlo_bytes.py"), str(f)],
        capture_output=True, text=True, cwd=str(REPO), timeout=120)
    assert p.returncode == 0, p.stderr
    assert "exponential" in p.stdout
