"""Contract checker + repo lints (repro.analysis).

Four layers:

  * hlo_stats parsing regressions: async -start/-done collective pairs
    count ONCE (bytes, counts, crosspod attribution), permute pairs come
    off the -start/sync line only, and the ``input_output_alias`` header
    block parses into flat parameter numbers;
  * contracts unit surface: pair rules, flat donation offsets, clause
    evaluation against hand-written HLO, host-f64 comm checks, compile
    counters — no multi-device host needed;
  * lint rules: paired good/bad fixtures under tests/analysis_fixtures/
    per rule (the bad thread fixture models the exact unguarded
    cross-thread read repro.serving.driver shipped with), the checked
    baseline workflow, and the repo-is-clean gate over src/repro;
  * the tools/run_analysis.py entry point: green on this repo, nonzero
    on a seeded violation and on a stale waiver, and (slow) the decode
    rows of the contract matrix end to end in a forced-8-device
    subprocess.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts, lint
from repro.launch import hlo_stats

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"


# ---------------------------------------------------------------------------
# hlo_stats parsing regressions
# ---------------------------------------------------------------------------

# CPU lowers collectives synchronously, so the async split is pinned with
# a hand-written module in real HLO syntax: the -start op's result is an
# (operand, result) tuple — summing its shape tokens double counts.
ASYNC_HLO = textwrap.dedent("""\
    HloModule async_pair

    ENTRY main {
      %p0 = f32[8,128]{1,0} parameter(0)
      %p1 = f32[4]{0} parameter(1)
      %ag-start = (f32[8,128]{1,0}, f32[16,128]{1,0}) all-gather-start(%p0), replica_groups={{0,1}}, dimensions={0}
      %ag-done = f32[16,128]{1,0} all-gather-done(%ag-start)
      %cp-start = (f32[4]{0}, f32[4]{0}) collective-permute-start(%p1), source_target_pairs={{0,1},{1,0}}
      %cp-done = f32[4]{0} collective-permute-done(%cp-start)
      %sync = f32[8,128]{1,0} all-reduce(%p0), replica_groups={{0,1}}, to_apply=%add
      ROOT %out = (f32[16,128]{1,0}, f32[4]{0}, f32[8,128]{1,0}) tuple(%ag-done, %cp-done, %sync)
    }
    """)


def test_async_pairs_count_once():
    b = hlo_stats.collective_bytes(ASYNC_HLO)
    assert b["all-gather"] == 16 * 128 * 4  # -done result only, not the
    assert b["collective-permute"] == 4 * 4  # (operand, result) tuple
    assert b["all-reduce"] == 8 * 128 * 4
    c = hlo_stats.collective_counts(ASYNC_HLO)
    assert c["all-gather"] == 1
    assert c["collective-permute"] == 1
    assert c["all-reduce"] == 1
    assert c["all-to-all"] == 0 and c["reduce-scatter"] == 0


def test_async_crosspod_attributed_from_start_line():
    # group metadata lives on -start, bytes on -done: the pairing must
    # attribute the -done bytes to the -start line's groups
    b = hlo_stats.collective_bytes(ASYNC_HLO, pod_boundary=1)
    assert b["crosspod"] == 16 * 128 * 4 + 4 * 4 + 8 * 128 * 4


def test_permute_pairs_come_from_start_not_done():
    pairs = hlo_stats.collective_permute_pairs(ASYNC_HLO)
    assert pairs == [[(0, 1), (1, 0)]]


def test_collective_result_dtypes():
    dts = hlo_stats.collective_result_dtypes(ASYNC_HLO)
    assert dts == {"all-gather": {"f32"}, "collective-permute": {"f32"},
                   "all-reduce": {"f32"}}


def test_wide_tuple_collective_counted():
    """XLA numbers the elements of a six-plus-element tuple shape with
    ``/*index=5*/`` comments; such a combined all-reduce (PAPA's parameter
    pmean on the TPU) must still be counted, with every element's bytes."""
    elems = ", ".join(f"bf16[4,{k}]{{1,0:T(8,128)(2,1)}}" for k in range(1, 6))
    hlo = (f"  %ar = ({elems}, /*index=5*/bf16[4,6]{{1,0}}) "
           f"all-reduce(%a, %b, %c, %d, %e, %f), replica_groups={{{{0,1}}}}, "
           f"to_apply=%add\n")
    assert hlo_stats.collective_counts(hlo)["all-reduce"] == 1
    assert hlo_stats.collective_bytes(hlo)["all-reduce"] == 4 * 21 * 2
    assert hlo_stats.collective_result_dtypes(hlo) == {"all-reduce": {"bf16"}}


def test_input_output_alias_parsing():
    hlo = ('HloModule m, input_output_alias={ {0}: (0, {}, may-alias), '
           '{1}: (2, {}, must-alias) }, entry_computation_layout={()}\n')
    assert hlo_stats.input_output_aliased_params(hlo) == {0, 2}
    assert hlo_stats.input_output_aliased_params("HloModule m\n") == set()


# ---------------------------------------------------------------------------
# contracts: rules, donation offsets, clause evaluation
# ---------------------------------------------------------------------------


def test_pair_rules():
    ring = contracts.stage_ring(4)
    assert ring.ok(0, 4) and ring.ok(5, 1) and not ring.ok(0, 1)
    fwd = contracts.forward_hop(4)
    assert fwd.ok(0, 1) and fwd.ok(2, 3)
    assert not fwd.ok(3, 4)  # never wraps past the last stage
    assert not fwd.ok(1, 0)
    bwd = contracts.backward_hop(4)
    assert bwd.ok(1, 0) and bwd.ok(3, 2)
    assert not bwd.ok(0, -1) and not bwd.ok(4, 3)  # stage 0 never sends back
    with pytest.raises(ValueError):
        contracts.PairRule("sideways", 4)
    for r in (ring, fwd, bwd):
        assert r.describe()


def test_flat_donated_params_offsets():
    args = ({"a": jnp.zeros(1), "b": jnp.zeros(1)},  # leaves 0-1
            jnp.zeros(1),                            # leaf 2
            [jnp.zeros(1), jnp.zeros(1)])            # leaves 3-4
    assert contracts.flat_donated_params(args, (0,)) == (0, 1)
    assert contracts.flat_donated_params(args, (1,)) == (2,)
    assert contracts.flat_donated_params(args, (0, 2)) == (0, 1, 3, 4)
    with pytest.raises(ValueError):
        contracts.flat_donated_params(args, (3,))


def test_check_hlo_clauses():
    contract = contracts.Contract(
        name="toy",
        require_collectives=("all-gather",),
        forbid_collectives=("all-to-all",),
        counts={"all-reduce": (1, 2), "collective-permute": 1},
        permute_rules=(contracts.stage_ring(2),),
        collective_dtypes={"all-gather": ("f32",)},
    )
    rep = contracts.check_hlo(ASYNC_HLO, contract, donated_params=(0,),
                              raise_on_violation=False)
    # {0,1} pairs are ens hops on a 2-stage ring view: 0%2 != 1%2
    assert not rep.ok
    assert any("permute pair (0 -> 1)" in p for p in rep.problems)
    assert any("donated parameters [0]" in p for p in rep.problems)
    with pytest.raises(contracts.ContractViolation) as ei:
        contracts.check_hlo(ASYNC_HLO, contract, donated_params=(0,))
    assert "toy" in str(ei.value)

    ok = contracts.Contract(
        name="toy-ok",
        require_collectives=("all-gather", "collective-permute"),
        forbid_collectives=("all-to-all", "reduce-scatter"),
        counts={"all-reduce": (1, 2), "collective-permute": 1},
        permute_rules=(contracts.stage_ring(1),),
        collective_dtypes={"all-gather": ("f32",)},
    )
    assert contracts.check_hlo(ASYNC_HLO, ok).ok


def test_check_hlo_flags_wrong_dtype_and_count():
    bad_dtype = contracts.Contract(
        name="dtype", collective_dtypes={"all-gather": ("bf16",)})
    rep = contracts.check_hlo(ASYNC_HLO, bad_dtype, raise_on_violation=False)
    assert any("moves dtypes ['f32']" in p for p in rep.problems)
    bad_count = contracts.Contract(name="count", counts={"all-reduce": 3})
    rep = contracts.check_hlo(ASYNC_HLO, bad_count, raise_on_violation=False)
    assert rep.problems == ["all-reduce: 1 ops, expected 3"]


def test_lower_and_check_donation_roundtrip():
    # a jit with honored donation passes; stating donation the program
    # cannot honor (no matching output) fails — the silent-drop detector
    def inplace(x, y):
        return x + jnp.sum(y)

    c = contracts.Contract(name="donate", donate_argnums=(0,))
    args = (jnp.zeros((8,), jnp.float32), jnp.ones((4,), jnp.float32))
    assert contracts.lower_and_check(inplace, args, c).ok

    def consumes(x, y):
        # no output matches x's (8,) shape: nothing to alias into
        return jnp.sum(x) + y

    rep = contracts.lower_and_check(consumes, args, c,
                                    raise_on_violation=False)
    assert not rep.ok and "donation was dropped" in rep.problems[0]


def test_host_comm_f64_contract():
    contracts.check_host_comm_f64({"comm": 1.5, "total": 0.0})
    with pytest.raises(contracts.ContractViolation, match="not builtin"):
        contracts.check_host_comm_f64({"comm": np.float64(1.5)})
    with pytest.raises(contracts.ContractViolation, match="not builtin"):
        contracts.check_host_comm_f64({"comm": jnp.float32(1.5)})
    with pytest.raises(contracts.ContractViolation, match="not finite"):
        contracts.check_host_comm_f64({"comm": float("inf")})


def test_replay_comm_is_bit_exact():
    per = 0.1  # not exactly representable: order and width must match
    gates = [True, False, True, True, False, True]
    expect = 0.0
    for g in gates:
        if g:
            expect += per
    assert contracts.replay_comm(per, gates) == expect
    assert contracts.replay_comm(per, []) == 0.0


def test_check_compile_count():
    contracts.check_compile_count("x", 1, 1)
    contracts.check_compile_count("x", 2, (1, 2))
    with pytest.raises(contracts.ContractViolation, match="allows 1"):
        contracts.check_compile_count("x", 2, 1)
    with pytest.raises(contracts.ContractViolation, match=r"allows \[1, 2\]"):
        contracts.check_compile_count("x", 3, (1, 2))


# ---------------------------------------------------------------------------
# lint rules: paired fixtures
# ---------------------------------------------------------------------------


def _fixture(name):
    return lint.lint_file(FIXTURES / name, root=REPO)


def test_tracer_hazard_fixture_pair():
    bad = _fixture("tracer_bad.py")
    assert all(v.rule == "tracer-hazard" for v in bad)
    assert {(v.func, v.detail) for v in bad} == {
        ("bad_step", "float()"), ("bad_step", "np.mean"),
        ("bad_step", "time.time"), ("bad_step", "random.random"),
        ("bad_scan.body", ".item()"),
    }
    assert _fixture("tracer_good.py") == []


def test_f32_accumulator_fixture_pair():
    bad = _fixture("accumulator_bad.py")
    assert all(v.rule == "f32-accumulator" for v in bad)
    assert {(v.func, v.detail) for v in bad} == {
        ("<module>", "comm_total:float32"),
        ("track", "bytes_total:float32"),
        ("Meter.__init__", "comm_scalars:float32"),
    }
    assert _fixture("accumulator_good.py") == []


def test_thread_discipline_fixture_pair():
    """The bad fixture reproduces the driver defect the lint caught in
    this repo: a pump thread mutates under the lock while the caller
    thread polls the same attrs unguarded."""
    bad = _fixture("threads_bad.py")
    assert {(v.func, v.detail) for v in bad} == {
        ("BadDriver.has_work", "attr:_pending"),
        ("BadDriver.snapshot", "attr:metrics"),
    }
    assert all(v.rule == "thread-discipline" for v in bad)
    assert _fixture("threads_good.py") == []


def test_violation_keys_are_line_free():
    v = _fixture("threads_bad.py")[0]
    assert str(v.line) not in v.key.split(":")
    assert v.key == f"thread-discipline:{v.path}:{v.func}:{v.detail}"


def test_baseline_workflow(tmp_path):
    base = tmp_path / "baseline.txt"
    viols = _fixture("threads_bad.py")
    base.write_text("# header comment\n" +
                    f"{viols[0].key}  # known-benign poll, bounded staleness\n")
    loaded = lint.load_baseline(base)
    assert loaded == {viols[0].key: "known-benign poll, bounded staleness"}
    remaining, stale = lint.apply_baseline(viols, loaded)
    assert viols[0] not in remaining and len(remaining) == len(viols) - 1
    assert stale == []
    # a waiver for vanished code is itself an error
    remaining, stale = lint.apply_baseline([], loaded)
    assert stale == [viols[0].key]
    # unexplained waivers are a parse error, not a style nit
    base.write_text(f"{viols[0].key}\n")
    with pytest.raises(ValueError, match="justification"):
        lint.load_baseline(base)


def test_repo_is_clean():
    """src/repro passes all three lint rules modulo the checked baseline
    (currently empty) — the same gate tools/run_analysis.py enforces."""
    violations = lint.lint_tree(REPO)
    baseline = lint.load_baseline(REPO / "tools" / "analysis_baseline.txt")
    remaining, stale = lint.apply_baseline(violations, baseline)
    assert remaining == [], "\n".join(str(v) for v in remaining)
    assert stale == [], f"stale baseline entries: {stale}"


# ---------------------------------------------------------------------------
# tools/run_analysis.py entry point
# ---------------------------------------------------------------------------


def _run_analysis(*args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "run_analysis.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


def test_run_analysis_lint_lane_green():
    r = _run_analysis("--skip-contracts")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "lint OK" in r.stdout


def test_run_analysis_exits_nonzero_on_seeded_violation(tmp_path):
    bad = tmp_path / "src" / "repro"
    bad.mkdir(parents=True)
    (bad / "seeded.py").write_text(
        "import numpy as np\ncomm_total = np.float32(0.0)\n")
    r = _run_analysis("--skip-contracts", "--root", str(tmp_path))
    assert r.returncode == 1
    assert "f32-accumulator" in r.stderr and "comm_total" in r.stderr


def test_run_analysis_flags_stale_baseline(tmp_path):
    (tmp_path / "src" / "repro").mkdir(parents=True)
    (tmp_path / "src" / "repro" / "ok.py").write_text("x = 1\n")
    base = tmp_path / "base.txt"
    base.write_text("f32-accumulator:gone.py:f:comm_total:float32  # old\n")
    r = _run_analysis("--skip-contracts", "--root", str(tmp_path),
                      "--baseline", str(base))
    assert r.returncode == 1
    assert "stale baseline entry" in r.stderr


def test_run_analysis_entries_stay_in_sync():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "run_analysis_mod", REPO / "tools" / "run_analysis.py")
    saved = os.environ.get("XLA_FLAGS")
    try:
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:  # the tool injects device-forcing XLA_FLAGS at import
        if saved is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = saved
    from repro.analysis import matrix

    assert mod.MATRIX_ENTRIES == matrix.ENTRIES
    assert set(mod.build_parser().parse_args([]).__dict__) >= {
        "root", "baseline", "rules", "entries", "skip_lint",
        "skip_contracts"}


@pytest.mark.slow
def test_run_analysis_contract_lane_decode_rows():
    """The decode rows of the contract matrix, end to end through the CI
    entry point (forced-8-device subprocess; the train rows run in the
    CI multidevice lane and repro.analysis.matrix's own run)."""
    r = _run_analysis("--skip-lint", "--entries", "scan_decode",
                      "continuous_decode")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "contract scan_decode OK" in r.stdout
    assert "contract continuous_decode OK" in r.stdout


@pytest.mark.slow
def test_matrix_catches_seeded_contract_violation():
    """A program whose HLO breaks its stated contract makes
    lower_and_check raise — driven through the real serving program with
    a deliberately wrong contract (donation on the token buffer, which
    is freshly allocated and can never alias)."""
    from repro.configs.base import ModelConfig
    from repro.models import transformer as M
    from repro.serving import engine as E

    cfg = ModelConfig(name="tiny", d_model=32, d_ff=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, vocab_size=64,
                      max_position=128)
    params_sds = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    cache_sds = jax.eval_shape(lambda: M.init_cache(cfg, 2, 12))
    key_dtype = jax.eval_shape(lambda: jax.random.key(0)).dtype
    args = (params_sds, jax.ShapeDtypeStruct((2, 4), jnp.int32), cache_sds,
            jax.ShapeDtypeStruct((2, 1, cfg.vocab_size), jnp.float32),
            jax.ShapeDtypeStruct((2,), key_dtype),
            jax.ShapeDtypeStruct((), jnp.float32))
    program = E._decode_program(cfg, False, 4, 8, True)
    wrong = contracts.Contract(name="seeded", donate_argnums=(1,))
    with pytest.raises(contracts.ContractViolation,
                       match="donation was dropped"):
        contracts.lower_and_check(program, args, wrong)
