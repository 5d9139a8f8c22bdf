"""The contract checker checks the checker: green on the live repo, red on
known-bad fixtures (DESIGN.md §12).

Each fixture is a minimal source snippet seeded with exactly one contract
break — a misaligned alias map, a missing donation, a use-after-donate, an
oracle signature drift, a scalar-prefetch reorder, an unguarded mirror
write — and the test asserts the checker reports the expected rule id at
the fixture's defect, not merely *some* failure.
"""
from __future__ import annotations

import textwrap

import pytest

from repro.analysis import contracts
from repro.analysis.contracts import (
    CANONICAL_PREFETCH_ORDER,
    DELEGATING_ENTRY_POINTS,
    EXPECTED_PREFETCH,
    ContractEntry,
    check_dispatch_source,
    check_kernel_source,
    check_mirror_source,
    check_repo,
    pallas_sites,
    signature_violations,
)


def _rules(violations):
    return {v.rule for v in violations}


# ---------------------------------------------------------------------------
# Live repo must be clean — the checker is a blocking CI step
# ---------------------------------------------------------------------------
def test_repo_is_contract_clean():
    violations = [v for v in check_repo() if not v.advisory]
    assert violations == [], "\n".join(str(v) for v in violations)


def test_wirepath_pallas_site_coverage_is_exhaustive():
    """The alias/prefetch audit provably covers every pallas_call in
    kernels/wirepath.py: each discovered site is audited with a resolved
    prefetch count and a non-empty alias map, and together with the
    delegating host entries the contract spans all wire-path entry
    points (wirepath_round, multigroup_, cohort_, shard_slab_,
    persistent_wirepath_round)."""
    sites = [
        s for s in pallas_sites() if s.file.endswith("wirepath.py")
    ]
    assert len(sites) >= 3
    entries = {s.entry for s in sites}
    assert entries == {
        "cohort_wirepath_round",
        "persistent_wirepath_round",
        "packed_shard_round",
        "acceptor_vote_all_window",
    }
    for s in sites:
        assert s.num_scalar_prefetch is not None, s
        assert s.aliases, f"{s.entry}: no input_output_aliases audited"
        assert s.kernel is not None, s
    covered = set(EXPECTED_PREFETCH) | set(DELEGATING_ENTRY_POINTS)
    assert covered >= {
        "wirepath_round",
        "multigroup_wirepath_round",
        "cohort_wirepath_round",
        "shard_slab_round",
        "persistent_wirepath_round",
        "packed_shard_round",
    }


def test_all_kernel_pallas_sites_are_audited():
    # every kernels/*.py pallas_call shows up in the exhaustiveness surface
    sites = pallas_sites()
    files = {s.file.rsplit("/", 1)[-1] for s in sites}
    assert {
        "acceptor.py", "coordinator.py", "learner.py", "digest.py",
        "wirepath.py",
    } <= files


def test_canonical_order_is_self_consistent():
    for name, classes in EXPECTED_PREFETCH.items():
        assert contracts._is_subsequence(
            classes, CANONICAL_PREFETCH_ORDER
        ), name


# ---------------------------------------------------------------------------
# Red fixtures: alias map defects
# ---------------------------------------------------------------------------
_ALIAS_FIXTURE = """
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cohort_wirepath_kernel(gsel_ref, ni_ref, crnd_ref, q_ref, alive_ref,
                            lim_ref, *rest):
    pass


def cohort_wirepath_round(gs, ni, cr, q, al, lim, st, out_shape, idx):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(1,),
        in_specs=[pl.BlockSpec((1, 8), idx)],
        out_specs=[pl.BlockSpec((1, 8), idx)],
    )
    fn = pl.pallas_call(
        _cohort_wirepath_kernel,
        grid_spec=grid_spec,
        out_shape=[out_shape],
        input_output_aliases={ALIASES},
    )
    return fn(DISPATCH)
"""


def _alias_fixture(aliases: str, dispatch: str = "gs, ni, cr, q, al, lim, st"):
    src = _ALIAS_FIXTURE.replace("{ALIASES}", aliases).replace(
        "DISPATCH", dispatch
    )
    return check_kernel_source(textwrap.dedent(src), "fixture.py")


def test_fixture_clean_alias_map_passes():
    violations, sites = _alias_fixture("{6: 0}")
    assert not violations
    assert len(sites) == 1
    assert sites[0].num_scalar_prefetch == 6
    assert sites[0].aliases == ((6, 0),)


def test_fixture_alias_inside_prefetch_window():
    # the off-by-one this checker exists for: a prefetch scalar grows the
    # vector but the alias map still points at the old input index
    violations, _ = _alias_fixture("{5: 0}")
    assert "ALIAS-OFFSET" in _rules(violations)


def test_fixture_alias_out_of_range():
    violations, _ = _alias_fixture("{7: 0}")
    assert "ALIAS-OFFSET" in _rules(violations)


def test_fixture_alias_not_onto_leading_outputs():
    violations, _ = _alias_fixture("{6: 1}")
    assert "ALIAS-BIJECTION" in _rules(violations)


def test_fixture_alias_spec_shape_mismatch():
    src = _ALIAS_FIXTURE.replace(
        "out_specs=[pl.BlockSpec((1, 8), idx)]",
        "out_specs=[pl.BlockSpec((2, 8), idx)]",
    ).replace("{ALIASES}", "{6: 0}").replace(
        "DISPATCH", "gs, ni, cr, q, al, lim, st"
    )
    violations, _ = check_kernel_source(textwrap.dedent(src), "fixture.py")
    assert "ALIAS-OFFSET" in _rules(violations)


def test_fixture_dispatch_arity_drift():
    # one operand short: a state input was dropped from the dispatch
    violations, _ = _alias_fixture("{6: 0}", dispatch="gs, ni, cr, q, al, lim")
    assert "ALIAS-ARITY" in _rules(violations)


# ---------------------------------------------------------------------------
# Red fixture: scalar-prefetch reorder
# ---------------------------------------------------------------------------
def test_fixture_prefetch_reorder():
    # watermark and round swapped at the dispatch site
    violations, _ = _alias_fixture(
        "{6: 0}", dispatch="gs, cr, ni, q, al, lim, st"
    )
    assert "PREFETCH-ORDER" in _rules(violations)


def test_fixture_prefetch_kernel_param_reorder():
    src = _ALIAS_FIXTURE.replace(
        "def _cohort_wirepath_kernel(gsel_ref, ni_ref, crnd_ref, q_ref, "
        "alive_ref,\n                            lim_ref, *rest):",
        "def _cohort_wirepath_kernel(gsel_ref, crnd_ref, ni_ref, q_ref, "
        "alive_ref,\n                            lim_ref, *rest):",
    ).replace("{ALIASES}", "{6: 0}").replace(
        "DISPATCH", "gs, ni, cr, q, al, lim, st"
    )
    violations, _ = check_kernel_source(textwrap.dedent(src), "fixture.py")
    assert "PREFETCH-ORDER" in _rules(violations)


def test_fixture_delegation_scalar_reorder():
    src = textwrap.dedent(
        """
        def wirepath_round(ni, cr, q, al, lim, values):
            return multigroup_wirepath_round(cr, ni, q, al, values, lim)
        """
    )
    violations, _ = check_kernel_source(src, "fixture.py")
    assert "PREFETCH-ORDER" in _rules(violations)


# ---------------------------------------------------------------------------
# Red fixtures: donation audit
# ---------------------------------------------------------------------------
def test_fixture_missing_donation():
    src = textwrap.dedent(
        """
        import jax
        from repro.kernels import ops as kops


        class Plane:
            def __init__(self):
                self._fused = jax.jit(kops.fused_round)
        """
    )
    violations = check_dispatch_source(src, "fixture.py")
    assert "DONATE-MISSING" in _rules(violations)


def test_fixture_donating_non_state_operand():
    src = textwrap.dedent(
        """
        import jax
        from repro.kernels import ops as kops


        class Plane:
            def __init__(self):
                self._fused = jax.jit(kops.fused_round, donate_argnums=(3,))
        """
    )
    violations = check_dispatch_source(src, "fixture.py")
    assert "DONATE-STATE" in _rules(violations)


def test_fixture_use_after_donate():
    src = textwrap.dedent(
        """
        import jax
        from repro.kernels import ops as kops


        class Plane:
            def __init__(self):
                self._fused = jax.jit(
                    kops.fused_round, donate_argnums=(1, 2)
                )

            def step(self, values, active, alive, q):
                out = self._fused(
                    self.cstate, self.stack, self.lstate,
                    values, active, alive, q,
                )
                stale = self.stack.rnd
                return out, stale
        """
    )
    violations = check_dispatch_source(src, "fixture.py")
    assert "DONATE-USE" in _rules(violations)


def test_fixture_donate_then_reassign_is_clean():
    src = textwrap.dedent(
        """
        import jax
        from repro.kernels import ops as kops


        class Plane:
            def __init__(self):
                self._fused = jax.jit(
                    kops.fused_round, donate_argnums=(1, 2)
                )

            def step(self, values, active, alive, q):
                c, self.stack, self.lstate, f, i, w, v = self._fused(
                    self.cstate, self.stack, self.lstate,
                    values, active, alive, q,
                )
                return f, i, self.stack.rnd
        """
    )
    violations = check_dispatch_source(src, "fixture.py")
    assert "DONATE-USE" not in _rules(violations)


# ---------------------------------------------------------------------------
# Red fixture: oracle signature drift
# ---------------------------------------------------------------------------
def _entry(fn, oracle, **kw):
    kw.setdefault("state_args", ())
    kw.setdefault("extra", ())
    kw.setdefault("oracle_extra", ())
    kw.setdefault("strict_order", True)
    kw.setdefault("reason", None)
    return ContractEntry(name=fn.__name__, fn=fn, oracle=oracle, **kw)


def test_fixture_oracle_default_drift():
    def wrapper(state, msgs, enabled=None, limit=None):
        pass

    def oracle(state, msgs, enabled=None, limit=0):
        pass

    violations = signature_violations(_entry(wrapper, oracle))
    assert _rules(violations) == {"ORACLE-PARITY"}
    assert any("limit" in v.message for v in violations)


def test_fixture_oracle_arity_drift():
    def wrapper(state, msgs, enabled=None):
        pass

    def oracle(state, msgs):
        pass

    violations = signature_violations(_entry(wrapper, oracle))
    assert "ORACLE-PARITY" in _rules(violations)


def test_fixture_oracle_name_drift():
    def wrapper(state, messages):
        pass

    def oracle(state, msgs):
        pass

    violations = signature_violations(_entry(wrapper, oracle))
    assert "ORACLE-PARITY" in _rules(violations)


def test_fixture_matching_signatures_pass():
    def wrapper(state, msgs, enabled=None, limit=None, group_block=1):
        pass

    def oracle(state, msgs, enabled=None, limit=None):
        pass

    violations = signature_violations(
        _entry(wrapper, oracle, extra=("group_block",))
    )
    assert violations == []


def test_fixture_unlinked_without_reason():
    def wrapper(state):
        pass

    violations = signature_violations(_entry(wrapper, None))
    assert "ORACLE-PARITY" in _rules(violations)


# ---------------------------------------------------------------------------
# Red fixtures: kernel purity + mirror guard
# ---------------------------------------------------------------------------
def test_fixture_kernel_python_branch_on_ref():
    src = textwrap.dedent(
        """
        def _bad_kernel(x_ref, o_ref):
            if x_ref[0] > 0:
                o_ref[0] = 1
        """
    )
    violations, _ = check_kernel_source(src, "fixture.py")
    assert "KERNEL-PURITY" in _rules(violations)


def test_fixture_kernel_static_metadata_branch_is_clean():
    src = textwrap.dedent(
        """
        def _ok_kernel(x_ref, o_ref):
            if x_ref.dtype == "int32":
                o_ref[0] = x_ref[0]
        """
    )
    violations, _ = check_kernel_source(src, "fixture.py")
    assert "KERNEL-PURITY" not in _rules(violations)


def test_fixture_kernel_host_idiom_is_advisory():
    src = textwrap.dedent(
        """
        import numpy as np


        def _chatty_kernel(x_ref, o_ref):
            o_ref[0] = np.sum(x_ref[0])
        """
    )
    violations, _ = check_kernel_source(src, "fixture.py")
    host = [v for v in violations if v.rule == "KERNEL-HOST"]
    assert host and all(v.advisory for v in host)


def test_fixture_unguarded_mirror_write():
    src = textwrap.dedent(
        """
        class Plane:
            def step(self):
                self.next_inst_host[0] = 5
        """
    )
    violations = check_mirror_source(src, "fixture.py")
    assert "MIRROR-GUARD" in _rules(violations)


def test_fixture_guarded_mirror_write_is_clean():
    src = textwrap.dedent(
        """
        from repro.analysis.contracts import mirror_guard


        class Plane:
            def __init__(self):
                self.next_inst_host = [0]

            @mirror_guard
            def step(self):
                self.next_inst_host[0] = 5
        """
    )
    violations = check_mirror_source(src, "fixture.py")
    assert violations == []


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------
def test_cli_exits_zero_on_live_repo(capsys):
    assert contracts.main([]) == 0
    out = capsys.readouterr().out
    assert "contracts OK" in out


@pytest.mark.parametrize("rule", sorted(contracts.RULES))
def test_rule_catalogue_has_descriptions(rule):
    assert contracts.RULES[rule]
