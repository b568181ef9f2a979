"""Tests for the static-analysis subsystem (repro.analysis).

Every rule gets both directions: the real tree passes, and a planted
violation (fixture file, illegal tile config, or poisoned traced
function) trips the exact rule id.
"""

import json
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from repro.analysis import __main__ as analysis_cli
from repro.analysis import graph_audit, kernel_lint, seams
from repro.analysis.findings import RULES, Finding
from repro.kernels import tuning

FIXTURES = Path(__file__).parent / "fixtures" / "analysis"

EXPECTED_FIXTURE_RULES = {
    "bad_assert.py": "RS101",
    "bad_free.py": "RS102",
    "bad_admission.py": "RS103",
    "bad_wallclock.py": "RS104",
    "bad_numpy_in_jit.py": "RS105",
}


# ------------------------------------------------------------- seam lint
def test_repo_tree_is_clean():
    findings = seams.scan_tree()
    assert findings == [], [str(f) for f in findings]


@pytest.mark.parametrize("fixture,rule", sorted(EXPECTED_FIXTURE_RULES.items()))
def test_fixture_trips_rule(fixture, rule):
    findings = seams.scan_file(FIXTURES / fixture)
    rules = {f.rule for f in findings}
    assert rule in rules, (fixture, [str(f) for f in findings])


def test_every_seam_rule_has_a_fixture():
    covered = set(EXPECTED_FIXTURE_RULES.values())
    seam_rules = {r for r in RULES if r.startswith("RS")}
    assert covered == seam_rules


def test_admission_fixture_flags_both_run_and_override():
    findings = seams.scan_file(FIXTURES / "bad_admission.py")
    msgs = [f.message for f in findings if f.rule == "RS103"]
    assert len(msgs) == 2
    assert any("run never calls" in m for m in msgs)
    assert any("admission_error override" in m for m in msgs)


def test_scheduler_validate_seam_satisfies_rs103():
    """The role-composed engines reach admission checks through an
    extracted Scheduler (``sched.validate(requests)``) rather than a
    direct ``self._validate`` call; RS103 accepts that seam."""
    src = (
        "class RoleEngine:\n"
        "    def run(self, requests):\n"
        "        sched = Scheduler(self)\n"
        "        reqs, rejected = sched.validate(requests)\n"
        "        return reqs\n"
    )
    assert seams.scan_source(src, "mod.py") == []


def test_pragma_suppresses_rule():
    src = "def f(x):\n    assert x  # repro: allow=RS101\n"
    assert seams.scan_source(src, "mod.py") == []


def test_pragma_on_previous_line_and_wildcard():
    src = "def f(x):\n    # repro: allow=*\n    assert x\n"
    assert seams.scan_source(src, "mod.py") == []


def test_pragma_for_other_rule_does_not_suppress():
    src = "def f(x):\n    assert x  # repro: allow=RS102\n"
    findings = seams.scan_source(src, "mod.py")
    assert [f.rule for f in findings] == ["RS101"]


def test_clock_classes_exempt_from_wallclock_rule():
    src = (
        "import time\n"
        "from repro.serving.request import SimClock\n"
        "class WallClock:\n"
        "    def now(self):\n"
        "        return time.perf_counter()\n"
    )
    assert seams.scan_source(src, "serving/clock.py") == []


def test_release_pages_exempt_from_free_rule():
    src = (
        "class PagedEngine:\n"
        "    def _release_pages(self, alloc, rid):\n"
        "        alloc.free(rid)\n"
    )
    assert seams.scan_source(src, "mod.py") == []


def test_numpy_outside_jit_not_flagged():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "def host_side(x):\n"
        "    return np.asarray(x)\n"
        "def device_side(x):\n"
        "    return x * 2\n"
        "f = jax.jit(device_side)\n"
    )
    assert seams.scan_source(src, "mod.py") == []


def test_jit_decorator_forms_detected():
    src = (
        "import functools\n"
        "import numpy as np\n"
        "import jax\n"
        "@functools.partial(jax.jit, static_argnums=0)\n"
        "def step(n, state):\n"
        "    return np.add(state, n)\n"
    )
    findings = seams.scan_source(src, "mod.py")
    assert [f.rule for f in findings] == ["RS105"]


# ------------------------------------------------------------ kernel lint
def _flash_dims(dtype="float32"):
    return dict(B=1, Sq=2048, Sk=2048, Hq=32, Hkv=8, D=128, dtype=dtype)


def test_defaults_accepted_on_canonical_shapes():
    findings = kernel_lint.check_defaults("tpu")
    assert findings == [], [str(f) for f in findings]


def test_flash_misaligned_tile_rejected():
    findings = kernel_lint.check_config(
        "flash_attention_fwd", _flash_dims(), {"block_q": 100, "block_k": 128}, "tpu"
    )
    rules = {f.rule for f in findings}
    assert "RK003" in rules  # 100 not a multiple of the 8-sublane
    assert "RK001" in rules  # and 2048 % 100 != 0


def test_flash_vmem_overflow_rejected():
    findings = kernel_lint.check_config(
        "flash_attention_fwd", _flash_dims(), {"block_q": 2048, "block_k": 2048}, "tpu"
    )
    rules = {f.rule for f in findings}
    assert "RK002" in rules  # (2048, 2048) f32 intermediates


def test_flash_default_tile_accepted():
    findings = kernel_lint.check_config(
        "flash_attention_fwd",
        _flash_dims(),
        tuning.DEFAULTS["flash_attention_fwd"],
        "tpu",
    )
    assert findings == [], [str(f) for f in findings]


def test_rwkv_oversized_chunk_rejected():
    dims = dict(B=1, T=2048, H=32, K=64, V=64, dtype="float32")
    findings = kernel_lint.check_config("wkv6_fwd", dims, {"chunk": 1024}, "tpu")
    rules = {f.rule for f in findings}
    assert "RK002" in rules  # (1024, 1024, 64) fallback tensor


def test_rwkv_default_chunk_accepted():
    dims = dict(B=1, T=2048, H=32, K=64, V=64, dtype="float32")
    assert (
        kernel_lint.check_config("wkv6_fwd", dims, tuning.DEFAULTS["wkv6_fwd"], "tpu")
        == []
    )


def test_rmsnorm_vmem_overflow_rejected():
    dims = dict(rows=65536, d=512, dtype="float32")
    findings = kernel_lint.check_config(
        "rmsnorm_fwd", dims, {"block_rows": 65536}, "tpu"
    )
    assert "RK002" in {f.rule for f in findings}


def test_rmsnorm_misaligned_rows_rejected():
    dims = dict(rows=8192, d=512, dtype="float32")
    findings = kernel_lint.check_config("rmsnorm_fwd", dims, {"block_rows": 100}, "tpu")
    assert "RK003" in {f.rule for f in findings}


def test_rmsnorm_auto_clamp_fits_wide_models():
    # the historical 256-row default overflows at d=4096/f32; the auto
    # path must clamp it to a block that fits the capability budget
    br = tuning.resolve_rmsnorm_rows(None, rows=8192, d=4096, dtype="float32")
    assert br < 256
    caps = tuning.capabilities("tpu")
    need = 2 * caps.pipeline_buffers * caps.padded_bytes((br, 4096), "float32")
    need += caps.padded_bytes((br, 4096), "float32")
    assert need <= caps.vmem_bytes


def test_rmsnorm_explicit_rows_not_clamped():
    assert tuning.resolve_rmsnorm_rows(4096, rows=8192, d=4096, dtype="float32") == 4096


def test_paged_oversized_pages_per_block_rejected():
    dims = dict(
        B=8, Hq=32, Hkv=8, D=128, L=10, P=512, ps=16, npag=512, dtype="float32"
    )
    findings = kernel_lint.check_config(
        "paged_attention_fwd", dims, {"pages_per_block": 512}, "tpu"
    )
    assert "RK002" in {f.rule for f in findings}  # 1024 page DMAs resident at once


def test_paged_default_accepted():
    dims = dict(
        B=8, Hq=32, Hkv=8, D=128, L=10, P=512, ps=16, npag=128, dtype="float32"
    )
    assert (
        kernel_lint.check_config(
            "paged_attention_fwd", dims, tuning.DEFAULTS["paged_attention_fwd"], "tpu"
        )
        == []
    )


@pytest.mark.parametrize("layers", [1, 10])
def test_paged_plan_reads_the_stacked_pool_at_its_layer(layers):
    """The plan mirrors the shipped kernel: K/V blocks of one page of one
    layer on the stacked (L, P, ps, Hkv, D) pool, addressed through the
    layer and block-table scalar operands, and RK004 sees the layer axis."""
    dims = dict(
        B=4, Hq=32, Hkv=8, D=128, L=layers, P=64, ps=16, npag=8, dtype="float32"
    )
    (plan,) = kernel_lint.plan_paged_attention(dims, {"pages_per_block": 2})
    pages = [b for b in plan.blocks if "_pages[" in b.name]
    assert len(pages) == 4
    for blk in pages:
        assert blk.array_shape == (layers, 64, 16, 8, 128)
        assert blk.block_shape == (1, 1, 16, 8, 128)
        assert blk.index_map(3, 3) == (layers - 1, 63, 0, 0, 0)
    caps = tuning.capabilities("tpu")
    assert kernel_lint._check_plan(plan, caps) == []
    inner = pages[0].index_map
    pages[0].index_map = lambda b, j: (inner(b, j)[0] + 1,) + inner(b, j)[1:]
    rules = {f.rule for f in kernel_lint._check_plan(plan, caps)}
    assert rules == {"RK004"}


def test_unsupported_dtype_rejected():
    dims = dict(rows=1024, d=512, dtype="float64")
    findings = kernel_lint.check_config("rmsnorm_fwd", dims, {"block_rows": 256}, "tpu")
    assert "RK005" in {f.rule for f in findings}


def test_index_map_bounds_checked():
    # white-box: a plan whose index_map walks off the operand
    plan = kernel_lint.Plan(
        kernel="synthetic",
        path="x.py",
        grid=(4,),
        blocks=[kernel_lint.Block("x", (256, 128), (64, 128), lambda i: (i + 1, 0))],
    )
    findings = kernel_lint._check_plan(plan, tuning.capabilities("tpu"))
    assert {f.rule for f in findings} == {"RK004"}


def test_grid_corner_sampling_covers_large_grids():
    pts = kernel_lint._grid_samples((1000, 2))
    assert (0, 0) in pts and (999, 1) in pts
    assert len(pts) <= 16


def test_tuned_cache_entries_checked(tmp_path, monkeypatch):
    monkeypatch.setenv(tuning.ENV_VAR, str(tmp_path))
    sig = tuning.attention_signature(
        (1, 2048, 32, 128), (1, 2048, 8, 128), "float32", causal=True, window=0
    )
    entries = {
        tuning.entry_key("flash_attention_fwd", sig): {
            "config": {"block_q": 256, "block_k": 256}
        },
    }
    (tmp_path / "cpu.json").write_text(
        json.dumps({"version": 1, "env": {}, "entries": entries})
    )
    assert kernel_lint.check_tuned_cache("cpu") == []

    entries[tuning.entry_key("flash_attention_fwd", sig)] = {
        "config": {"block_q": 100, "block_k": 128}
    }
    (tmp_path / "cpu.json").write_text(
        json.dumps({"version": 1, "env": {}, "entries": entries})
    )
    findings = kernel_lint.check_tuned_cache("cpu")
    assert findings and {f.rule for f in findings} >= {"RK003"}
    assert all("cpu.json" in f.path for f in findings)


def test_gpu_capability_entry_differs():
    caps = tuning.capabilities("gpu")
    assert caps.vmem_bytes < tuning.capabilities("tpu").vmem_bytes
    assert caps.lane == 64


# ------------------------------------------------------------ graph audit
def test_clean_function_passes():
    assert graph_audit.audit_function("f", lambda x: x * 2 + 1, jnp.ones((4, 4))) == []


def test_host_callback_flagged():
    def noisy(x):
        jax.debug.print("x = {}", x)
        return x * 2

    findings = graph_audit.audit_function("noisy", noisy, jnp.ones(4))
    assert any(f.rule == "RG001" for f in findings)


def test_f64_leak_flagged():
    def leak(x):
        return x.astype(jnp.float64).sum()

    with jax.enable_x64():
        findings = graph_audit.audit_function("leak", leak, jnp.ones(4))
    assert any(f.rule == "RG002" for f in findings)


def test_weak_type_churn_flagged():
    jitted = jax.jit(lambda x: x * 2)
    findings = graph_audit.check_cache_growth("doubler", jitted, [(1,), (1.0,)])
    assert [f.rule for f in findings] == ["RG003"]


def test_stable_signature_no_churn():
    jitted = jax.jit(lambda x: x * 2)
    a = jnp.arange(4.0)
    assert graph_audit.check_cache_growth("doubler", jitted, [(a,), (a + 1,)]) == []


_SYNTH_COLLECTIVE_HLO = """\
HloModule synth

ENTRY %main (p0: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128] parameter(0)
  %ar = f32[128,128] all-reduce(%p0), replica_groups={{0,1}}, \
to_apply=%add
  ROOT %r = f32[128,128] add(%ar, %p0)
}
"""


def test_collective_in_single_device_hlo_flagged():
    findings = graph_audit.audit_hlo_text("step", _SYNTH_COLLECTIVE_HLO)
    assert any(f.rule == "RG004" for f in findings)


def test_collective_ok_when_multi_device_expected():
    assert (
        graph_audit.audit_hlo_text(
            "step", _SYNTH_COLLECTIVE_HLO, expect_single_device=False
        )
        == []
    )


def test_outfeed_in_hlo_flagged():
    text = "HloModule m\n\nENTRY %e () -> f32[] {\n  %o = outfeed()\n}\n"
    findings = graph_audit.audit_hlo_text("step", text)
    assert any(f.rule == "RG005" for f in findings)


def test_compiled_hlo_of_clean_step_passes():
    findings = graph_audit.audit_hlo("mul", lambda x: x @ x, jnp.ones((8, 8)))
    assert findings == [], [str(f) for f in findings]


def test_decode_step_audit_clean():
    findings = graph_audit.audit_decode_step()
    assert findings == [], [str(f) for f in findings]


def test_engine_steady_state_no_recompiles():
    findings = graph_audit.audit_engine_steady_state()
    assert findings == [], [str(f) for f in findings]


# -------------------------------------------------------------------- CLI
def test_cli_clean_tree_exits_zero(capsys):
    rc = analysis_cli.main(["--layer", "seams", "--layer", "kernels"])
    assert rc == 0
    assert "clean" in capsys.readouterr().err


def test_cli_seeded_violation_exits_nonzero(capsys):
    rc = analysis_cli.main(["--layer", "seams", "--root", str(FIXTURES)])
    assert rc == 1
    out = capsys.readouterr().out
    for rule in EXPECTED_FIXTURE_RULES.values():
        assert rule in out


def test_cli_json_output_is_jsonl(capsys):
    rc = analysis_cli.main(["--layer", "seams", "--root", str(FIXTURES), "--json"])
    assert rc == 1
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    for ln in lines:
        rec = json.loads(ln)
        assert {"rule", "path", "line", "message", "name"} <= set(rec)
    found = {r["rule"] for r in map(json.loads, lines)}
    assert found >= set(EXPECTED_FIXTURE_RULES.values())


def test_cli_list_rules(capsys):
    assert analysis_cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULES:
        assert rule in out


def test_cli_internal_error_exits_two(capsys):
    rc = analysis_cli.main(["--layer", "seams", "--root", "/nonexistent/tree"])
    # an empty/missing tree is not an error, it is just zero findings —
    # but a root that is a file with bad syntax must not crash either
    assert rc in (0, 2)


def test_finding_str_is_clickable():
    f = Finding("RS101", "src/repro/x.py", 42, "boom")
    assert str(f).startswith("src/repro/x.py:42: RS101")


def test_rules_catalog_complete():
    prefixes = {r[:2] for r in RULES}
    assert prefixes == {"RK", "RG", "RS"}
    assert all(RULES[r] for r in RULES)
