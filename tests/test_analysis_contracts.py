"""dgclint layer 2: contract primitives, the HLO parsers, and the
standing suite over the real flat train step.

The suite test here IS the repo's invariant mechanism (ISSUE 3): one
sparse exchange, telemetry compiles away, donation aliases, barrier-free
fused epilogue, trace stability across config variants, collective-free
shard_state."""

import jax
import jax.numpy as jnp
import pytest

from dgc_tpu.analysis import hlo
from dgc_tpu.analysis.contracts import (Contract, ContractViolation,
                                        RecompileGuard, trace_count)

# --------------------------------------------------------------------- #
# hlo text parsers (synthetic inputs)                                    #
# --------------------------------------------------------------------- #

_LOWERED = """\
module @jit_f {
  func.func public @main(%arg0: tensor<8xf32>) -> tensor<8xf32> {
    %0 = stablehlo.constant dense<1.0> : tensor<8xf32>
    %1 = "stablehlo.all_gather"(%arg0) : (tensor<8xf32>) -> tensor<8xf32>
    %2 = "stablehlo.all_reduce"(%1) : (tensor<8xf32>) -> tensor<8xf32>
    %3 = "stablehlo.all_reduce"(%2) : (tensor<8xf32>) -> tensor<8xf32>
    %4 = stablehlo.optimization_barrier %3 : tensor<8xf32>
    %5 = stablehlo.add %4, %0 : tensor<8xf32>
    return %5 : tensor<8xf32>
  }
}
"""

_COMPILED_DONATED = (
    "HloModule jit_f, is_scheduled=true, "
    "input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, may-alias) }"
    ", entry_computation_layout={(f32[8]{0})->f32[8]{0}}")

_COMPILED_PLAIN = ("HloModule jit_f, is_scheduled=true, "
                   "entry_computation_layout={(f32[8]{0})->f32[8]{0}}")


def test_op_counts_and_normalization():
    c = hlo.op_counts(_LOWERED)
    assert c["all-gather"] == 1 and c["all-reduce"] == 2
    assert c["optimization-barrier"] == 1 and c["add"] == 1
    assert hlo.count_op(_LOWERED, "all_gather") == 1
    assert hlo.normalize_op("stablehlo.all_gather") == "all-gather"


def test_collective_counts_zero_filled():
    c = hlo.collective_counts(_LOWERED)
    assert c["all-to-all"] == 0 and c["reduce-scatter"] == 0


def test_has_f64():
    assert not hlo.has_f64(_LOWERED)
    assert hlo.has_f64("%0 = stablehlo.constant : tensor<4xf64>")
    assert hlo.has_f64("param = f64[8]{0} parameter(0)")
    assert not hlo.has_f64("bf16[8] and f16[8] are fine")


def test_donated_params_parses_nested_braces():
    assert hlo.donated_params(_COMPILED_DONATED) == [0, 2]
    assert hlo.donated_params(_COMPILED_PLAIN) == []


# --------------------------------------------------------------------- #
# Contract primitives (no lowering: inject texts)                        #
# --------------------------------------------------------------------- #

def _contract(**kw):
    return Contract("t", lowered_text=_LOWERED,
                    compiled_text=_COMPILED_DONATED, **kw)


def test_contract_collectives_pass_and_fail():
    assert _contract().expects(
        collectives={"all-gather": 1, "all_reduce": 2}).check() == []
    bad = _contract().expects(collectives={"all-gather": 3}).check()
    assert len(bad) == 1 and "expected 3" in bad[0]


def test_contract_forbid_and_require_ops():
    assert _contract().expects(require_ops=["all_gather"]).check() == []
    assert "forbidden op" in _contract().expects(
        forbid_ops=["optimization_barrier"]).check()[0]
    assert "required op" in _contract().expects(
        require_ops=["reduce-scatter"]).check()[0]


def test_contract_forbid_substrings_and_f64():
    assert _contract().expects(forbid_substrings=["telemetry"],
                               no_f64=True).check() == []
    assert "forbidden substring" in _contract().expects(
        forbid_substrings=["all_gather"]).check()[0]


def test_contract_donation_expectations():
    assert _contract().expects(donation=[0, 2]).check() == []
    assert "not aliased" in _contract().expects(donation=[1]).check()[0]
    plain = Contract("p", compiled_text=_COMPILED_PLAIN)
    assert plain.expects(donation=[]).check() == []
    assert "silently dropped" in Contract(
        "p2", compiled_text=_COMPILED_PLAIN).expects(
        donation=[0]).check()[0]
    assert "expected no aliasing" in _contract().expects(
        donation=[]).check()[0]


def test_contract_identical_and_delta():
    same = Contract("b", lowered_text=_LOWERED)
    assert _contract().expects(identical_to=same).check() == []
    other = Contract("c", lowered_text=_LOWERED.replace(
        "add", "subtract"))
    bad = _contract().expects(identical_to=other).check()
    assert "byte-identical" in bad[0]
    assert _contract().expects(
        collectives_delta=(other, {"all-reduce": 0})).check() == []
    assert "delta" in _contract().expects(
        collectives_delta=(other, {"all-reduce": 1})).check()[0]


def test_enforce_raises_with_all_violations():
    with pytest.raises(ContractViolation) as ei:
        _contract().expects(collectives={"all-gather": 9},
                            forbid_ops=["add"]).enforce()
    assert len(ei.value.violations) == 2


# --------------------------------------------------------------------- #
# recompile guard on live jits                                           #
# --------------------------------------------------------------------- #

def test_trace_count_requires_jit_wrapper():
    with pytest.raises(TypeError):
        trace_count(lambda x: x)


def test_recompile_guard_passes_on_cache_hits():
    f = jax.jit(lambda x: x * 2)
    with RecompileGuard(f, expect=1):
        f(jnp.ones((4,)))
        f(jnp.zeros((4,)))          # same shape: cache hit


def test_recompile_guard_traps_shape_retrace():
    f = jax.jit(lambda x: x * 2)
    with pytest.raises(ContractViolation, match="cache key"):
        with RecompileGuard(f, expect=1):
            f(jnp.ones((4,)))
            f(jnp.ones((5,)))       # new shape: second trace


# --------------------------------------------------------------------- #
# the standing suite over the real step (ISSUE 3 acceptance pins)        #
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def suite_results(mesh8):
    from dgc_tpu.analysis.suite import run_contract_suite
    return run_contract_suite(mesh8)


def test_contract_suite_all_green(suite_results):
    failed = {n: v for n, v in suite_results if v}
    assert not failed, failed


@pytest.mark.parametrize("pin", [
    "flat-step-one-sparse-exchange",
    "telemetry-on-exactly-one-pmean",
    "telemetry-off-compiles-away",
    "donated-state-aliases-outputs",
    "fused-epilogue-no-opt-barriers",
    "recompile-guard-same-shapes",
    "shard-state-collective-free",
    "control-plane-host-only",
    "pack-pass-under-fwd_bwd.pack",
])
def test_suite_covers_named_pin(suite_results, pin):
    assert pin in {n for n, _ in suite_results}


def test_fused_epilogue_contract_standalone():
    from dgc_tpu.analysis.suite import _epilogue_contract
    _epilogue_contract().enforce()


def test_recompile_guard_across_config_variants(mesh8):
    """Flipping donate/use_dropout/telemetry must each build a step that
    traces exactly once for same-shape calls (the flags are Python-static,
    never part of a per-call cache key)."""
    from dgc_tpu.analysis.suite import build_fixture

    for kw in (dict(donate=False, telemetry=False),
               dict(donate=False, telemetry=True),
               dict(donate=False, use_dropout=True),
               dict(donate=True,)):
        state, step, _, (images, labels, key) = build_fixture(mesh8, **kw)
        with RecompileGuard(step, expect=1, name=str(kw)):
            out = step(state, images, labels, key)
            # thread the fresh state through: under donate=True the input
            # buffers are consumed by the first call
            step(out[0], images, labels, jax.random.PRNGKey(3))
