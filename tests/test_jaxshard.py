"""jaxshard: the static SPMD/sharding analyzer and its committed plan.

Covers the ISSUE-19 contract:
  - propagation exactness: hand-computed per-axis wire bytes on a
    2-axis mesh matmul chain,
  - implicit collectives are charged the same bytes as an explicitly
    collectived (shard_map + psum) twin,
  - donation-defeat detector true positive AND true negative,
  - reshape factor-group propagation unit cases,
  - registry/plan full coverage in both directions,
  - CLI exit-code semantics (0 clean / 1 violation / 2 usage),
  - diff_plans structural + tolerance drift detection,
  - crosscheck against the committed jaxcost budget.
"""
import copy
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.analysis import jaxshard
from paddle_tpu.parallel import set_global_mesh
from paddle_tpu.parallel import shard_map

pytestmark = pytest.mark.lint


@pytest.fixture(autouse=True)
def _clear_mesh():
    set_global_mesh(None)
    yield
    set_global_mesh(None)


REPO = pathlib.Path(__file__).resolve().parent.parent
JAXSHARD_CLI = REPO / "tools" / "jaxshard.py"
PLAN_FILE = REPO / "shardplan.json"
BUDGET_FILE = REPO / "jaxcost_budget.json"


def _mesh2x4():
    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devs, ("x", "y"))


def _ns(mesh, *axes):
    return NamedSharding(mesh, P(*axes))


# ------------------------------------------------------ propagation
class TestPropagation:
    def test_matmul_chain_hand_computed(self):
        """(a @ b) @ c on a 2x4 ("x","y") mesh.

        a[64,32]@[x,-] x b[32,16]@[-,y] -> ab[64,16]@[x,y]   (free dims
        sharded, contraction unsharded: no collective).
        ab@[x,y] x c[16,8]@[y,-] contracts the y-sharded dim ->
        partial-sum out[64,8]@[x,-]: implicit psum over y, charged
        2 x global result bytes = 2*64*8*4 = 4096.
        out_shardings replicated -> all_gather over x of the x-sharded
        2048B result = 2048 wire bytes.
        """
        mesh = _mesh2x4()
        fn = jax.jit(
            lambda a, b, c: (a @ b) @ c,
            in_shardings=(_ns(mesh, "x", None), _ns(mesh, None, "y"),
                          _ns(mesh, "y", None)),
            out_shardings=_ns(mesh),
        )
        a = jnp.zeros((64, 32), jnp.float32)
        b = jnp.zeros((32, 16), jnp.float32)
        c = jnp.zeros((16, 8), jnp.float32)
        rep = jaxshard.analyze_jit(fn, a, b, c, name="t.chain",
                                   mesh=mesh)

        assert rep.mesh == {"x": 2, "y": 4}
        assert rep.implicit_axis_bytes == {"y": 4096, "x": 2048}
        assert rep.explicit_axis_bytes == {}
        assert rep.comm_bytes_total == 6144

        kinds = sorted((e.kind, tuple(sorted(e.axis_bytes)))
                       for e in rep.edges)
        assert kinds == [("all_gather", ("x",)), ("psum", ("y",))]

        # psum >= IMPLICIT_MIN_BYTES must surface as an unsuppressed
        # finding keyed by kind+axes
        keys = {f.key for f in rep.unsuppressed()}
        assert "implicit:psum:y" in keys
        assert "implicit:all_gather:x" in keys

        # per-device peak: every live buffer divided by its shard
        # factor, so it must come in well under the unsharded peak
        # (entry 10752B alone) while staying positive
        assert 0 < rep.per_device_peak_bytes < 8192

    def test_implicit_matches_explicit_twin(self):
        """A jit reduction over a sharded dim and its shard_map +
        lax.psum twin must charge identical per-axis wire bytes —
        the analyzer prices the collective, not the spelling."""
        mesh = _mesh2x4()
        n = 256
        g = jnp.zeros((8, n), jnp.float32)

        imp = jax.jit(lambda t: t.sum(axis=0),
                      in_shardings=(_ns(mesh, "x", None),),
                      out_shardings=_ns(mesh))
        rep_imp = jaxshard.analyze_jit(imp, g, name="t.imp", mesh=mesh)

        exp = jax.jit(shard_map(
            lambda t: jax.lax.psum(t.sum(axis=0), "x"),
            mesh=mesh, in_specs=(P("x", None),), out_specs=P(None),
            check_vma=False))
        rep_exp = jaxshard.analyze_jit(exp, g, name="t.exp", mesh=mesh)

        # 2 x the [n] f32 result over axis x = 2*256*4 = 2048B
        assert rep_imp.implicit_axis_bytes == {"x": 2048}
        assert rep_exp.explicit_axis_bytes == {"x": 2048}
        assert (rep_imp.implicit_axis_bytes["x"]
                == rep_exp.explicit_axis_bytes["x"])
        # the explicit twin carries no implicit edges at all
        assert rep_exp.implicit_axis_bytes == {}

    def test_reshape_factor_groups(self):
        sizes = {"x": 2, "y": 4}
        # merge: leading in-dim of the group keeps its sharding
        out, lost = jaxshard._map_reshape(
            (4, 8), (32,), (("x",), None), sizes)
        assert tuple(out) == (("x",),) and lost == []
        # merge: a non-leading sharded in-dim is re-tiled
        out, lost = jaxshard._map_reshape(
            (4, 8), (32,), (None, ("y",)), sizes)
        assert tuple(out) == (None,) and lost == ["y"]
        # split: sharding survives on the leading factor when the
        # shard count divides it
        out, lost = jaxshard._map_reshape(
            (32,), (4, 8), (("x",),), sizes)
        assert tuple(out) == (("x",), None) and lost == []
        # split: leading factor not divisible by the shard count
        out, lost = jaxshard._map_reshape(
            (32,), (2, 16), (("y",),), sizes)
        assert lost == ["y"]


# --------------------------------------------------------- donation
class TestDonation:
    def test_defeated_true_positive(self):
        """Donated invar held [x,-] aliasing an output held [-,y]:
        layouts differ across the aliasing, so XLA cannot reuse the
        buffer — donation:defeated must fire."""
        mesh = _mesh2x4()
        fn = jax.jit(lambda t: t * 2.0,
                     in_shardings=(_ns(mesh, "x", None),),
                     out_shardings=_ns(mesh, None, "y"),
                     donate_argnums=(0,))
        x = jnp.zeros((32, 32), jnp.float32)
        rep = jaxshard.analyze_jit(fn, x, name="t.don", mesh=mesh)
        keys = {f.key: f for f in rep.findings}
        assert "donation:defeated:0" in keys
        assert keys["donation:defeated:0"].nbytes == 32 * 32 * 4

    def test_reshard_true_positive(self):
        """Donated invar whose aliased output is produced sharded but
        held replicated: the gather lands in the donated buffer."""
        mesh = _mesh2x4()

        def body(t):
            return jax.lax.with_sharding_constraint(
                t * 2.0, _ns(mesh, "x", None))

        fn = jax.jit(body, in_shardings=(_ns(mesh),),
                     out_shardings=_ns(mesh), donate_argnums=(0,))
        x = jnp.zeros((32, 32), jnp.float32)
        rep = jaxshard.analyze_jit(fn, x, name="t.resh", mesh=mesh)
        assert any(f.key == "donation:reshard:0" for f in rep.findings)

    def test_matched_layout_true_negative(self):
        """Same sharded layout on both sides of the aliasing: no
        donation finding (the serving.cache_write.tp pattern)."""
        mesh = _mesh2x4()
        sh = _ns(mesh, "x", None)
        fn = jax.jit(lambda t: t * 2.0, in_shardings=(sh,),
                     out_shardings=sh, donate_argnums=(0,))
        x = jnp.zeros((32, 32), jnp.float32)
        rep = jaxshard.analyze_jit(fn, x, name="t.tn", mesh=mesh)
        assert not any(f.kind == "donation" for f in rep.findings)
        assert rep.edges == []

    def test_suppression_marks_and_reports_unused(self):
        mesh = _mesh2x4()
        fn = jax.jit(lambda t: t * 2.0,
                     in_shardings=(_ns(mesh, "x", None),),
                     out_shardings=_ns(mesh, None, "y"),
                     donate_argnums=(0,))
        x = jnp.zeros((32, 32), jnp.float32)
        rep = jaxshard.analyze_jit(
            fn, x, name="t.sup", mesh=mesh,
            suppress={"donation:defeated:0": "triaged: test",
                      "implicit:psum:zz": "stale key"})
        don = [f for f in rep.findings
               if f.key == "donation:defeated:0"]
        assert don and don[0].suppressed == "triaged: test"
        assert any("implicit:psum:zz" in n for n in rep.notes)


# ------------------------------------------------- plan + registry
class TestCommittedPlan:
    def test_plan_covers_registry_both_directions(self):
        assert PLAN_FILE.exists(), "shardplan.json must be committed"
        plan = json.loads(PLAN_FILE.read_text())
        assert plan["version"] == jaxshard.PLAN_VERSION
        names = set(jaxshard.registry_names())
        assert len(names) >= 8
        assert set(plan["programs"]) == names

    def test_every_committed_finding_is_triaged(self):
        plan = json.loads(PLAN_FILE.read_text())
        for name, entry in plan["programs"].items():
            for key, f in entry["findings"].items():
                assert f["suppressed"], (
                    f"{name}: {key} committed without a triage reason")

    def test_real_hits_are_documented(self):
        """The acceptance bar: the donation and implicit-collective
        detectors each have a triaged REAL hit in the committed plan."""
        plan = json.loads(PLAN_FILE.read_text())
        fsdp = plan["programs"]["train_step.fsdp_tp"]["findings"]
        assert "REAL HIT" in fsdp["donation:reshard:27"]["suppressed"]
        attn = plan["programs"]["serving.decode_attn.tp"]["findings"]
        assert "REAL HIT" in attn["implicit:psum:tp"]["suppressed"]

    def test_envelope_holds_for_every_program(self):
        plan = json.loads(PLAN_FILE.read_text())
        for name, entry in plan["programs"].items():
            assert entry["envelope_ok"], name
            assert 0 < entry["per_device_peak_bytes"] \
                <= plan["envelope_bytes"]

    def test_committed_shard_factors(self):
        factors = jaxshard.committed_shard_factors(str(PLAN_FILE))
        assert factors["train_step.fsdp_tp"] == {"sharding": 2,
                                                 "tp": 2}
        assert factors["serving.decode_qkv.tp"] == {"tp": 4}


class TestDiffPlans:
    @pytest.fixture()
    def committed(self):
        return json.loads(PLAN_FILE.read_text())

    def test_identical_plans_clean(self, committed):
        assert jaxshard.diff_plans(committed,
                                   copy.deepcopy(committed)) == []

    def test_coverage_both_directions(self, committed):
        cur = copy.deepcopy(committed)
        dropped = cur["programs"].pop("train_step.dp")
        cur["programs"]["train_step.new"] = dropped
        out = jaxshard.diff_plans(committed, cur)
        assert any("train_step.dp: committed but no longer" in v
                   for v in out)
        assert any("train_step.new: registry program missing" in v
                   for v in out)

    def test_structural_drift_is_exact(self, committed):
        cur = copy.deepcopy(committed)
        entry = cur["programs"]["train_step.fsdp_tp"]
        entry["mesh"] = {"sharding": 4, "tp": 2}
        entry["edge_count"] += 1
        out = jaxshard.diff_plans(committed, cur)
        assert any("mesh drift" in v for v in out)
        assert any("resharding edge count" in v for v in out)

    def test_byte_drift_tolerance(self, committed):
        cur = copy.deepcopy(committed)
        entry = cur["programs"]["collective.ring_attention"]
        base = entry["explicit_axis_bytes"]["sp"]
        # 4% rides inside the committed 5% tolerance
        entry["explicit_axis_bytes"]["sp"] = int(base * 1.04)
        assert not any("explicit_axis_bytes[sp]" in v
                       for v in jaxshard.diff_plans(committed, cur))
        # 6% does not
        entry["explicit_axis_bytes"]["sp"] = int(base * 1.06)
        assert any("explicit_axis_bytes[sp] drifted" in v
                   for v in jaxshard.diff_plans(committed, cur))

    def test_finding_and_suppression_drift(self, committed):
        cur = copy.deepcopy(committed)
        f = cur["programs"]["serving.decode_attn.tp"]["findings"]
        f["implicit:psum:tp"]["suppressed"] = None
        out = jaxshard.diff_plans(committed, cur)
        assert any("suppression changed" in v for v in out)
        del f["implicit:psum:tp"]
        out = jaxshard.diff_plans(committed, cur)
        assert any("finding keys drifted" in v for v in out)


class TestCrosscheck:
    def test_committed_artifacts_agree(self):
        budget = json.loads(BUDGET_FILE.read_text())
        assert jaxshard.crosscheck_with_budget(
            budget, str(PLAN_FILE)) == []
        # the check is live: the collective trio is present in both
        shared = (set(budget["programs"])
                  & set(json.loads(PLAN_FILE.read_text())["programs"]))
        assert shared >= {"collective.psum_tree",
                          "collective.ring_attention",
                          "collective.ulysses_attention"}

    def test_drift_detected(self):
        budget = json.loads(BUDGET_FILE.read_text())
        budget = copy.deepcopy(budget)
        budget["programs"]["collective.ring_attention"][
            "comm_bytes"] *= 2
        out = jaxshard.crosscheck_with_budget(budget, str(PLAN_FILE))
        assert any("collective.ring_attention" in v
                   and "drifted apart" in v for v in out)


# -------------------------------------------------------------- CLI
def _cli(*args):
    return subprocess.run(
        [sys.executable, str(JAXSHARD_CLI), *args],
        capture_output=True, text=True, timeout=600,
        cwd=str(REPO), env=dict(os.environ, JAX_PLATFORMS="cpu"))


class TestCLI:
    def test_plan_check_passes_on_committed_file(self):
        r = _cli("--plan", "check", "--format", "json")
        assert r.returncode == 0, r.stdout + r.stderr
        assert json.loads(r.stdout)["plan_violations"] == []

    def test_version_drift_fails_fast(self, tmp_path):
        plan = json.loads(PLAN_FILE.read_text())
        plan["version"] = jaxshard.PLAN_VERSION + 1
        stale = tmp_path / "shardplan.json"
        stale.write_text(json.dumps(plan))
        r = _cli("--plan", "check", "--plan-file", str(stale))
        assert r.returncode == 1
        assert "PLAN VIOLATION" in r.stdout
        assert "version" in r.stdout

    def test_programs_conflicts_with_plan(self):
        r = _cli("--plan", "check", "--programs", "train_step.dp")
        assert r.returncode == 2
        assert "conflicts" in r.stderr

    def test_unknown_program_is_usage_error(self):
        r = _cli("--programs", "no.such.program")
        assert r.returncode == 2
        assert "no.such.program" in r.stderr

    def test_list_programs(self):
        r = _cli("--list-programs")
        assert r.returncode == 0
        assert set(r.stdout.split()) == set(jaxshard.registry_names())
