"""Dataplane contract checker: static alias/donation/prefetch/oracle-parity
verification of the wire-path kernel stack (DESIGN.md §12).

CAANS-style dataplanes are only trustworthy when the compiled artifact
provably matches the protocol layout — the paper leans on P4's static
pipeline typing for this.  Our equivalent hazards are hand-maintained
Python conventions that no single test names:

  * every ``pallas_call``'s ``input_output_aliases`` map must stay a
    bijection onto the leading (state) outputs, with input indices offset
    by ``num_scalar_prefetch`` — a silent off-by-one after the next
    prefetch vector lands corrupts aliased device state;
  * every ``jax.jit`` dispatch of a kernel wrapper must donate exactly
    the aliased state operands, and the host must never read a donated
    array after the call site;
  * every kernel wrapper in ``kernels/ops.py`` must keep signature parity
    (names, arity, keyword defaults) with its jnp oracle in
    ``core/batched.py``;
  * every entry point's scalar-prefetch vector must keep ONE canonical
    relative order, declared once as data below;
  * kernel bodies must stay trace-pure, and host watermark/round/
    reclamation mirrors in ``core/api.py`` may only move inside
    dispatch-/guard-annotated methods.

This module enforces all of that mechanically, from source (``ast``) and
from live signatures (``inspect``):

    PYTHONPATH=src python -m repro.analysis.contracts   # exit 0 when clean
    python tools/check_contracts.py                     # same, path-free

Violations print as ``file:line: RULE-ID: message`` and the process exits
non-zero on any non-advisory finding.  Rule catalogue in ``RULES``.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import importlib
import inspect
import os
import re
import sys
from collections.abc import Callable, Iterable, Sequence
from typing import Any

RULES: dict[str, str] = {
    "ALIAS-BIJECTION": (
        "input_output_aliases must map distinct inputs onto exactly the "
        "leading outputs 0..m-1 (a bijection onto the state outputs)"
    ),
    "ALIAS-OFFSET": (
        "an aliased input index must equal num_scalar_prefetch + the "
        "positional offset of a state operand whose BlockSpec (shape and "
        "index map) is identical to the aliased output's"
    ),
    "ALIAS-ARITY": (
        "pallas_call arity drift: call-site args, in/out specs, out_shape "
        "and kernel parameters must all agree with num_scalar_prefetch"
    ),
    "PREFETCH-ORDER": (
        "scalar-prefetch vectors must follow the canonical class order "
        "declared in CANONICAL_PREFETCH_ORDER"
    ),
    "DONATE-STATE": (
        "donate_argnums must name only aliased state operands "
        "(stack/lstate/astate)"
    ),
    "DONATE-MISSING": (
        "a jax.jit dispatch of a kernel wrapper must donate exactly the "
        "wrapper's registered state operands"
    ),
    "DONATE-USE": (
        "host read of a donated state attribute after the donating "
        "dispatch and before reassignment (use-after-donate)"
    ),
    "ORACLE-PARITY": (
        "kernel wrapper and jnp oracle signatures (names, arity, keyword "
        "defaults) must match, modulo declared extras"
    ),
    "ORACLE-MISSING": (
        "every public entry in kernels/ops.py must be registered with "
        "@dataplane_contract"
    ),
    "KERNEL-PURITY": (
        "_*_kernel bodies must not Python-branch on Ref-derived values or "
        "mutate captured globals"
    ),
    "KERNEL-HOST": (
        "host-level idiom (numpy/.item()/device_get/print) inside a kernel "
        "body (advisory)"
    ),
    "MIRROR-GUARD": (
        "host watermark/round/reclamation mirrors may only be mutated in "
        "__init__ or @mirror_guard-annotated methods of core/api.py"
    ),
}


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    file: str
    line: int
    message: str
    advisory: bool = False

    def __str__(self) -> str:
        tag = " (advisory)" if self.advisory else ""
        return f"{self.file}:{self.line}: {self.rule}{tag}: {self.message}"


# ---------------------------------------------------------------------------
# Contract registry: @dataplane_contract links wrappers to their oracles
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ContractEntry:
    """One kernel wrapper's declared contract (see DESIGN.md §12).

    ``state_args`` are the wrapper parameters that alias device state in
    the underlying ``pallas_call`` — exactly the set a ``jax.jit``
    dispatch must donate.  ``extra``/``oracle_extra`` name parameters that
    intentionally exist on only one side of the wrapper/oracle pair;
    everything else must match.  ``strict_order=False`` relaxes the
    comparison to name-set + default equality for pairs whose parameter
    layouts legitimately differ (e.g. coordinator-stateless wrappers).
    """

    name: str
    fn: Callable[..., Any]
    oracle: Callable[..., Any] | None
    state_args: tuple[str, ...]
    extra: tuple[str, ...]
    oracle_extra: tuple[str, ...]
    strict_order: bool
    reason: str | None


CONTRACT_REGISTRY: dict[str, ContractEntry] = {}


def dataplane_contract(
    oracle: Callable[..., Any] | None = None,
    *,
    state_args: Sequence[str] = (),
    extra: Sequence[str] = (),
    oracle_extra: Sequence[str] = (),
    strict_order: bool = True,
    reason: str | None = None,
) -> Callable[[Callable[..., Any]], Callable[..., Any]]:
    """Register a ``kernels/ops.py`` wrapper against its jnp oracle.

    Returns the function unchanged (zero runtime cost; positional layouts
    seen by ``jax.jit(..., donate_argnums=...)`` are untouched).  A
    wrapper with no standalone oracle passes ``oracle=None`` with a
    ``reason`` documenting how it is verified instead.
    """

    def deco(fn: Callable[..., Any]) -> Callable[..., Any]:
        CONTRACT_REGISTRY[fn.__name__] = ContractEntry(
            name=fn.__name__,
            fn=fn,
            oracle=oracle,
            state_args=tuple(state_args),
            extra=tuple(extra),
            oracle_extra=tuple(oracle_extra),
            strict_order=strict_order,
            reason=reason,
        )
        return fn

    return deco


def mirror_guard(fn: Callable[..., Any]) -> Callable[..., Any]:
    """Marks a ``core/api.py`` method as an authorized mutation site for
    the host watermark/round/reclamation mirrors (dispatch methods that
    advance mirrors in lockstep with a device round, and guard/restore
    methods that re-seed them).  The mirror-pairing lint flags mirror
    writes anywhere else."""
    fn.__mirror_guard__ = True
    return fn


# ---------------------------------------------------------------------------
# Canonical dataplane layout — THE single source of truth (DESIGN.md §12)
# ---------------------------------------------------------------------------
# Relative order of scalar-prefetch classes on the wire.  Every prefetch
# vector (and every host entry-point's per-group scalar args) must list
# its scalars as an order-preserving subsequence of this tuple.
CANONICAL_PREFETCH_ORDER = (
    "gsel",       # selected group-block indices (grid compaction)
    "watermark",  # window base: next_inst / wni wave table / base slot
    "round",      # coordinator round (crnd)
    "quorum",     # f+1
    "alive",      # per-acceptor runtime liveness mask
    "limit",      # ring reclamation limit (first refused instance)
    "wen",        # persistent-wave per-round participation table
    "segids",     # per-lane local slab-row table (packed shard dispatch)
)

# ``enabled`` is deliberately NOT in the wire order: it is a host-side
# membership mask folded into ``round``/``watermark`` before prefetch
# (disabled groups ride at NO_ROUND with substituted lockstep bases), so
# host signatures may place it among trailing optionals.
_HOST_FOLDED = frozenset({"enabled"})

# Scalar-operand spelling -> class.  Kernel params are matched after
# stripping a trailing ``_ref``.
SCALAR_CLASSES: dict[str, str] = {
    "gs": "gsel", "gsel": "gsel", "blocks": "gsel",
    "ni": "watermark", "wni": "watermark", "wnik": "watermark",
    "base": "watermark", "next_inst": "watermark", "marks": "watermark",
    "cr": "round", "crnd": "round",
    "q": "quorum", "quorum": "quorum",
    "al": "alive", "alive": "alive",
    "lim": "limit", "limit": "limit", "reclaim_limit": "limit",
    "wen": "wen", "wenk": "wen",
    "en": "enabled", "enabled": "enabled",
    "seg": "segids", "segids": "segids",
}

# Per-entry expected prefetch vectors (class sequences), keyed by the
# wrapper function that owns the ``pallas_call``.  Each must be a
# subsequence of CANONICAL_PREFETCH_ORDER (asserted below).
EXPECTED_PREFETCH: dict[str, tuple[str, ...]] = {
    "cohort_wirepath_round": (
        "gsel", "watermark", "round", "quorum", "alive", "limit",
    ),
    "persistent_wirepath_round": (
        "gsel", "watermark", "round", "quorum", "alive", "limit", "wen",
    ),
    "acceptor_vote_all_window": ("watermark", "alive"),
    "packed_shard_round": (
        "watermark", "round", "quorum", "alive", "limit", "segids",
    ),
}

# Host entry points that delegate to another wire-path entry; the scalar
# args of the delegated call must stay in canonical relative order.
DELEGATING_ENTRY_POINTS: dict[str, str] = {
    "wirepath_round": "multigroup_wirepath_round",
    "multigroup_wirepath_round": "cohort_wirepath_round",
    "shard_slab_round": "multigroup_wirepath_round",
}

# core/fabric.py: the shard_map-replicated control scalars, leading params
# of the per-shard ``local`` body, in declared order.
FABRIC_REPLICATED_SCALARS = ("watermark", "round", "enabled", "alive", "limit")

# Wrapper params that may legally be donated by a jax.jit dispatch.
STATE_PARAM_NAMES = frozenset({"stack", "lstate", "astate"})

# Host mirrors paired with device watermark/round/reclamation state.
MIRROR_ATTRS = frozenset(
    {
        "next_inst_host",
        "_next_inst_host",
        "crnd_host",
        "reclaimed_host",
        "_reclaim_marks",
        "_reclaim_limit_dev",
    }
)

# Files whose jax.jit sites are kernel-wrapper dispatches (donation audit
# scope).
DONATION_FILES = ("core/api.py", "core/fabric.py")


def _is_subsequence(seq: Sequence[str], canon: Sequence[str]) -> bool:
    it = iter(canon)
    return all(c in it for c in seq)


def _self_check() -> None:
    for entry, classes in EXPECTED_PREFETCH.items():
        assert _is_subsequence(classes, CANONICAL_PREFETCH_ORDER), entry
    fab = [c for c in FABRIC_REPLICATED_SCALARS if c not in _HOST_FOLDED]
    assert _is_subsequence(fab, CANONICAL_PREFETCH_ORDER), "fabric scalars"


_self_check()


# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------
def _dotted(node: ast.expr) -> str | None:
    """'pl.pallas_call' for Attribute chains, 'name' for Names."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _const_int(node: ast.expr | None) -> int | None:
    if (
        isinstance(node, ast.Constant)
        and isinstance(node.value, int)
        and not isinstance(node.value, bool)
    ):
        return node.value
    return None


def _kwarg(call: ast.Call, name: str) -> ast.expr | None:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _funcdefs(tree: ast.AST) -> list[ast.FunctionDef]:
    return [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)]


def _assign_env(fdef: ast.FunctionDef) -> dict[str, ast.expr]:
    """name -> last assigned value expression, for simple Name targets."""
    env: dict[str, ast.expr] = {}
    for node in ast.walk(fdef):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt = node.targets[0]
            if isinstance(tgt, ast.Name):
                env[tgt.id] = node.value
    return env


def _scalar_class_of_name(name: str) -> str | None:
    stripped = name[:-4] if name.endswith("_ref") else name
    return SCALAR_CLASSES.get(stripped)


def _scalar_class_of_expr(node: ast.expr) -> str | None:
    """First recognizable scalar operand inside an expression, in source
    order — resilient to ``jnp.asarray(ni, jnp.int32).reshape(...)``
    wrapping (module names like ``jnp`` are not in the table)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            cls = _scalar_class_of_name(sub.id)
            if cls is not None:
                return cls
    return None


def _spec_fingerprint(spec: ast.expr) -> tuple[str, str] | None:
    """(block-shape dump, index-map identity) of a pl.BlockSpec call."""
    if not isinstance(spec, ast.Call) or len(spec.args) < 1:
        return None
    shape = ast.dump(spec.args[0])
    if len(spec.args) >= 2:
        idx = spec.args[1]
        index = idx.id if isinstance(idx, ast.Name) else ast.dump(idx)
    else:
        index = "<default>"
    return shape, index


def _spec_list(node: ast.expr | None) -> list[ast.expr] | None:
    if node is None:
        return None
    if isinstance(node, (ast.List, ast.Tuple)):
        return list(node.elts)
    return [node]  # single BlockSpec (e.g. one output)


def _out_shape_count(node: ast.expr | None, env: dict[str, ast.expr]) -> int | None:
    if isinstance(node, ast.Name):
        node = env.get(node.id)
    if node is None:
        return None
    if isinstance(node, (ast.List, ast.Tuple)):
        return len(node.elts)
    if isinstance(node, ast.ListComp) and len(node.generators) == 1:
        gen = node.generators[0]
        if (
            isinstance(gen.iter, ast.Call)
            and _dotted(gen.iter.func) == "range"
            and len(gen.iter.args) == 1
        ):
            return _const_int(gen.iter.args[0])
        return None
    if isinstance(node, ast.Call):
        return 1
    return None


@dataclasses.dataclass(frozen=True)
class PallasSite:
    """One audited ``pallas_call`` (exhaustiveness record)."""

    file: str
    line: int
    entry: str            # enclosing wrapper function
    kernel: str | None
    num_scalar_prefetch: int | None
    aliases: tuple[tuple[int, int], ...]


# ---------------------------------------------------------------------------
# Check family 1+3+4: pallas alias/arity audit, prefetch order, purity
# ---------------------------------------------------------------------------
def check_kernel_source(
    src: str,
    filename: str,
    expected_prefetch: dict[str, tuple[str, ...]] | None = None,
    delegations: dict[str, str] | None = None,
) -> tuple[list[Violation], list[PallasSite]]:
    """Audit every ``pallas_call`` in ``src`` plus kernel-body purity.

    Returns ``(violations, sites)`` where ``sites`` records each audited
    call site — the exhaustiveness test pins this list for
    ``kernels/wirepath.py``.
    """
    if expected_prefetch is None:
        expected_prefetch = EXPECTED_PREFETCH
    if delegations is None:
        delegations = DELEGATING_ENTRY_POINTS
    tree = ast.parse(src, filename=filename)
    out: list[Violation] = []
    sites: list[PallasSite] = []
    module_defs = {f.name: f for f in _funcdefs(tree)}

    for fdef in _funcdefs(tree):
        env = _assign_env(fdef)
        for node in ast.walk(fdef):
            if not isinstance(node, ast.Call):
                continue
            dn = _dotted(node.func)
            if dn is None or dn.split(".")[-1] != "pallas_call":
                continue
            out_v, site = _audit_pallas_site(
                node, fdef, env, module_defs, filename, expected_prefetch
            )
            out.extend(out_v)
            sites.append(site)
        if fdef.name in delegations:
            out.extend(
                _audit_delegation(fdef, delegations[fdef.name], filename)
            )

    out.extend(_check_kernel_purity(tree, filename))
    return out, sites


def _resolve_grid_spec(
    call: ast.Call, env: dict[str, ast.expr]
) -> tuple[int | None, list[ast.expr] | None, list[ast.expr] | None, int]:
    """(num_scalar_prefetch, in_specs, out_specs, n_scratch)."""
    gs = _kwarg(call, "grid_spec")
    if isinstance(gs, ast.Name):
        gs = env.get(gs.id)
    if isinstance(gs, ast.Call):
        n = _const_int(_kwarg(gs, "num_scalar_prefetch"))
        if n is None and _kwarg(gs, "num_scalar_prefetch") is None:
            n = 0
        in_specs = _spec_list(_kwarg(gs, "in_specs"))
        out_specs = _spec_list(_kwarg(gs, "out_specs"))
        scr = _kwarg(gs, "scratch_shapes")
        n_scratch = (
            len(scr.elts) if isinstance(scr, (ast.List, ast.Tuple)) else 0
        )
        return n, in_specs, out_specs, n_scratch
    # plain pallas_call(grid=..., in_specs=..., out_specs=...)
    in_specs = _spec_list(_kwarg(call, "in_specs"))
    out_specs = _spec_list(_kwarg(call, "out_specs"))
    return 0, in_specs, out_specs, 0


def _find_dispatch(
    pallas_call: ast.Call, fdef: ast.FunctionDef
) -> ast.Call | None:
    """The call applying the pallas-built function to its operands: either
    ``fn = pl.pallas_call(...)`` later invoked as ``fn(...)``, or the
    immediate ``pl.pallas_call(...)(...)`` form."""
    bound: str | None = None
    for node in ast.walk(fdef):
        if (
            isinstance(node, ast.Assign)
            and node.value is pallas_call
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            bound = node.targets[0].id
    for node in ast.walk(fdef):
        if not isinstance(node, ast.Call):
            continue
        if node.func is pallas_call:
            return node
        if (
            bound is not None
            and isinstance(node.func, ast.Name)
            and node.func.id == bound
        ):
            return node
    return None


def _kernel_def(
    pallas_call: ast.Call,
    fdef: ast.FunctionDef,
    module_defs: dict[str, ast.FunctionDef],
) -> ast.FunctionDef | None:
    if not pallas_call.args:
        return None
    kn = pallas_call.args[0]
    if not isinstance(kn, ast.Name):
        return None
    for nested in _funcdefs(fdef):
        if nested.name == kn.id and nested is not fdef:
            return nested
    return module_defs.get(kn.id)


def _audit_pallas_site(
    call: ast.Call,
    fdef: ast.FunctionDef,
    env: dict[str, ast.expr],
    module_defs: dict[str, ast.FunctionDef],
    filename: str,
    expected_prefetch: dict[str, tuple[str, ...]],
) -> tuple[list[Violation], PallasSite]:
    out: list[Violation] = []
    line = call.lineno
    n, in_specs, out_specs, n_scratch = _resolve_grid_spec(call, env)
    kdef = _kernel_def(call, fdef, module_defs)

    aliases: list[tuple[int, int]] = []
    adict = _kwarg(call, "input_output_aliases")
    if isinstance(adict, ast.Dict):
        keys = [_const_int(k) for k in adict.keys]
        vals = [_const_int(v) for v in adict.values]
        if None in keys or None in vals:
            out.append(
                Violation(
                    "ALIAS-BIJECTION", filename, line,
                    "input_output_aliases must be a literal int->int map",
                )
            )
        else:
            aliases = list(zip(keys, vals, strict=True))  # type: ignore[arg-type]
            out.extend(
                _check_alias_map(
                    aliases, n, in_specs, out_specs, filename, line
                )
            )

    # arity cross-checks (skipped where unresolvable)
    dispatch = _find_dispatch(call, fdef)
    if (
        dispatch is not None
        and n is not None
        and in_specs is not None
        and not any(isinstance(a, ast.Starred) for a in dispatch.args)
    ):
        want = n + len(in_specs)
        if len(dispatch.args) != want:
            out.append(
                Violation(
                    "ALIAS-ARITY", filename, dispatch.lineno,
                    f"dispatch passes {len(dispatch.args)} operands but "
                    f"num_scalar_prefetch({n}) + in_specs({len(in_specs)}) "
                    f"= {want}",
                )
            )
    n_out = _out_shape_count(_kwarg(call, "out_shape"), env)
    if n_out is not None and out_specs is not None and n_out != len(out_specs):
        out.append(
            Violation(
                "ALIAS-ARITY", filename, line,
                f"out_shape has {n_out} entries but out_specs has "
                f"{len(out_specs)}",
            )
        )
    if (
        kdef is not None
        and kdef.args.vararg is None
        and n is not None
        and in_specs is not None
        and out_specs is not None
    ):
        want = n + len(in_specs) + len(out_specs) + n_scratch
        got = len(kdef.args.args)
        if got != want:
            out.append(
                Violation(
                    "ALIAS-ARITY", filename, kdef.lineno,
                    f"kernel {kdef.name} has {got} params but prefetch({n}) "
                    f"+ inputs({len(in_specs)}) + outputs({len(out_specs)}) "
                    f"+ scratch({n_scratch}) = {want}",
                )
            )

    # prefetch-vector order for declared wire-path entries
    if fdef.name in expected_prefetch and n is not None:
        expect = expected_prefetch[fdef.name]
        if n != len(expect):
            out.append(
                Violation(
                    "PREFETCH-ORDER", filename, line,
                    f"{fdef.name}: num_scalar_prefetch is {n}, canonical "
                    f"vector is {expect} (len {len(expect)})",
                )
            )
        if dispatch is not None and len(dispatch.args) >= n:
            got_classes = tuple(
                _scalar_class_of_expr(a) for a in dispatch.args[:n]
            )
            if got_classes != expect:
                out.append(
                    Violation(
                        "PREFETCH-ORDER", filename, dispatch.lineno,
                        f"{fdef.name}: prefetch vector classes "
                        f"{got_classes} != canonical {expect}",
                    )
                )
        if kdef is not None:
            named = [a.arg for a in kdef.args.args]
            limit = len(named) if kdef.args.vararg is not None else n
            kc = tuple(
                _scalar_class_of_name(p) for p in named[: min(n, limit)]
            )
            if kc != expect[: len(kc)]:
                out.append(
                    Violation(
                        "PREFETCH-ORDER", filename, kdef.lineno,
                        f"kernel {kdef.name}: leading params map to {kc}, "
                        f"canonical prefix is {expect[: len(kc)]}",
                    )
                )

    site = PallasSite(
        file=filename,
        line=line,
        entry=fdef.name,
        kernel=kdef.name if kdef is not None else None,
        num_scalar_prefetch=n,
        aliases=tuple(aliases),
    )
    return out, site


def _check_alias_map(
    aliases: list[tuple[int, int]],
    n: int | None,
    in_specs: list[ast.expr] | None,
    out_specs: list[ast.expr] | None,
    filename: str,
    line: int,
) -> list[Violation]:
    out: list[Violation] = []
    keys = [k for k, _ in aliases]
    vals = [v for _, v in aliases]
    if len(set(keys)) != len(keys):
        out.append(
            Violation(
                "ALIAS-BIJECTION", filename, line,
                f"duplicate aliased inputs {sorted(keys)}",
            )
        )
    if sorted(vals) != list(range(len(vals))):
        out.append(
            Violation(
                "ALIAS-BIJECTION", filename, line,
                f"alias outputs {sorted(vals)} are not the contiguous "
                f"leading range 0..{len(vals) - 1}",
            )
        )
    if n is None or in_specs is None or out_specs is None:
        return out
    for k, v in aliases:
        if k < n:
            out.append(
                Violation(
                    "ALIAS-OFFSET", filename, line,
                    f"aliased input {k} lies inside the scalar-prefetch "
                    f"window (num_scalar_prefetch={n}) — off-by-one from "
                    f"a prefetch vector change",
                )
            )
            continue
        idx = k - n
        if idx >= len(in_specs) or v >= len(out_specs):
            out.append(
                Violation(
                    "ALIAS-OFFSET", filename, line,
                    f"alias {k}->{v} is out of range for in_specs"
                    f"[{len(in_specs)}]/out_specs[{len(out_specs)}] with "
                    f"num_scalar_prefetch={n}",
                )
            )
            continue
        fin = _spec_fingerprint(in_specs[idx])
        fout = _spec_fingerprint(out_specs[v])
        if fin is not None and fout is not None and fin != fout:
            out.append(
                Violation(
                    "ALIAS-OFFSET", filename, line,
                    f"alias {k}->{v}: input spec (shape {fin[0]}, index "
                    f"map {fin[1]}) != output spec (shape {fout[0]}, "
                    f"index map {fout[1]}) — the aliased operand is not "
                    f"the state operand at prefetch offset {idx}",
                )
            )
    return out


def _audit_delegation(
    fdef: ast.FunctionDef, target: str, filename: str
) -> list[Violation]:
    """Scalar args of a delegated wire-path call must stay in canonical
    relative order (``enabled`` excluded: host-folded, see above)."""
    out: list[Violation] = []
    for node in ast.walk(fdef):
        if not isinstance(node, ast.Call):
            continue
        dn = _dotted(node.func)
        if dn is None or dn.split(".")[-1] != target:
            continue
        classes = [
            c
            for c in (_scalar_class_of_expr(a) for a in node.args)
            if c is not None and c not in _HOST_FOLDED
        ]
        if not _is_subsequence(classes, CANONICAL_PREFETCH_ORDER):
            out.append(
                Violation(
                    "PREFETCH-ORDER", filename, node.lineno,
                    f"{fdef.name} -> {target}: scalar args in order "
                    f"{tuple(classes)} are not a subsequence of canonical "
                    f"{CANONICAL_PREFETCH_ORDER}",
                )
            )
    return out


# ---------------------------------------------------------------------------
# Check family 4: kernel-body purity
# ---------------------------------------------------------------------------
_KERNEL_NAME = re.compile(r"^_\w+_kernel$")
_STATIC_ATTRS = frozenset({"shape", "dtype", "ndim", "size", "at"})


def _dynamic_ref_use(node: ast.AST, params: frozenset[str]) -> bool:
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return False
    if isinstance(node, ast.Name):
        return node.id in params
    return any(
        _dynamic_ref_use(c, params) for c in ast.iter_child_nodes(node)
    )


def _check_kernel_purity(tree: ast.AST, filename: str) -> list[Violation]:
    out: list[Violation] = []
    for fdef in _funcdefs(tree):
        if not _KERNEL_NAME.match(fdef.name):
            continue
        params = frozenset(
            a.arg for a in fdef.args.args + fdef.args.kwonlyargs
        )
        for node in ast.walk(fdef):
            if isinstance(node, (ast.If, ast.While)) and _dynamic_ref_use(
                node.test, params
            ):
                out.append(
                    Violation(
                        "KERNEL-PURITY", filename, node.lineno,
                        f"{fdef.name}: Python {type(node).__name__} on a "
                        f"Ref-derived value — branch decisions must be "
                        f"jnp.where/pl.when so they trace",
                    )
                )
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                out.append(
                    Violation(
                        "KERNEL-PURITY", filename, node.lineno,
                        f"{fdef.name}: {type(node).__name__.lower()} "
                        f"mutation of captured state inside a kernel body",
                    )
                )
            elif isinstance(node, ast.Call):
                dn = _dotted(node.func) or ""
                leaf = dn.split(".")[-1]
                if (
                    dn.startswith("np.")
                    or leaf in {"item", "device_get"}
                    or dn == "print"
                ):
                    out.append(
                        Violation(
                            "KERNEL-HOST", filename, node.lineno,
                            f"{fdef.name}: host-level idiom `{dn}` inside "
                            f"a kernel body",
                            advisory=True,
                        )
                    )
    return out


# ---------------------------------------------------------------------------
# Check family 1b: donation audit + use-after-donate (dispatch files)
# ---------------------------------------------------------------------------
class _ImportResolver:
    """Resolves ``kops.fused_round`` / ``batched.acceptor_phase2_all`` /
    local function names to positional parameter lists (and, for
    ``kernels/ops.py`` targets, their registry entries) by importing the
    real modules — the checker runs with ``src`` importable."""

    def __init__(self, tree: ast.AST):
        self.aliases: dict[str, str] = {}
        self.local_defs: dict[str, ast.FunctionDef] = {
            f.name: f for f in _funcdefs(tree)
        }
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.aliases[a.asname or a.name] = (
                        f"{node.module}.{a.name}"
                    )
            elif isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = a.name

    def resolve(
        self, target: ast.expr
    ) -> tuple[list[str], ContractEntry | None] | None:
        """Positional param names of the jitted callable, or None."""
        dn = _dotted(target)
        if dn is None:
            return None
        if dn in self.local_defs:
            fdef = self.local_defs[dn]
            return [a.arg for a in fdef.args.args], None
        head, _, attr = dn.partition(".")
        mod_path = self.aliases.get(head)
        if mod_path is None or not attr:
            return None
        try:
            mod = importlib.import_module(mod_path)
            fn = getattr(mod, attr)
            sig = inspect.signature(fn)
        except Exception:
            return None
        params = [
            p.name
            for p in sig.parameters.values()
            if p.kind
            in (
                inspect.Parameter.POSITIONAL_ONLY,
                inspect.Parameter.POSITIONAL_OR_KEYWORD,
            )
        ]
        entry = None
        if mod_path.endswith("kernels.ops"):
            _load_ops_registry()
            entry = CONTRACT_REGISTRY.get(attr)
        return params, entry


def _donate_positions(call: ast.Call) -> list[int] | None:
    node = _kwarg(call, "donate_argnums")
    if node is None:
        return None
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = [_const_int(e) for e in node.elts]
        return None if None in vals else vals  # type: ignore[return-value]
    v = _const_int(node)
    return None if v is None else [v]


def _jit_calls(tree: ast.AST) -> list[ast.Call]:
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            dn = _dotted(node.func)
            if dn in ("jax.jit", "jit") and node.args:
                out.append(node)
    return out


def check_dispatch_source(
    src: str,
    filename: str,
    resolver: _ImportResolver | None = None,
) -> list[Violation]:
    """Donation audit over every ``jax.jit(..., donate_argnums=...)`` in a
    dispatch file, plus the per-class use-after-donate lint."""
    tree = ast.parse(src, filename=filename)
    if resolver is None:
        resolver = _ImportResolver(tree)
    out: list[Violation] = []
    for call in _jit_calls(tree):
        resolved = resolver.resolve(call.args[0])
        positions = _donate_positions(call)
        if resolved is None:
            continue
        params, entry = resolved
        donated: set[str] = set()
        if positions is not None:
            for p in positions:
                if p >= len(params):
                    out.append(
                        Violation(
                            "DONATE-STATE", filename, call.lineno,
                            f"donate_argnums position {p} is out of range "
                            f"for {_dotted(call.args[0])} "
                            f"({len(params)} positional params)",
                        )
                    )
                    continue
                donated.add(params[p])
            bad = donated - STATE_PARAM_NAMES
            if bad:
                out.append(
                    Violation(
                        "DONATE-STATE", filename, call.lineno,
                        f"{_dotted(call.args[0])} donates non-state "
                        f"operand(s) {sorted(bad)} — only aliased state "
                        f"({sorted(STATE_PARAM_NAMES)}) may be donated",
                    )
                )
        if entry is not None:
            want = set(entry.state_args)
            if donated != want:
                missing = sorted(want - donated)
                extra = sorted((donated - want) & STATE_PARAM_NAMES)
                parts = []
                if missing:
                    parts.append(f"missing {missing}")
                if extra:
                    parts.append(f"extraneous {extra}")
                if parts:
                    out.append(
                        Violation(
                            "DONATE-MISSING", filename, call.lineno,
                            f"jit of kernel wrapper {entry.name} must "
                            f"donate exactly its aliased state operands "
                            f"{sorted(want)}: " + ", ".join(parts),
                        )
                    )
    out.extend(_check_use_after_donate(tree, filename, resolver))
    return out


def _self_attr(node: ast.expr) -> str | None:
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def _donating_attrs_of_class(
    cdef: ast.ClassDef, resolver: _ImportResolver
) -> dict[str, frozenset[str]]:
    """attr name -> donated param names, from ``self.X = jax.jit(...,
    donate_argnums=...)`` statements anywhere in ``__init__``."""
    out: dict[str, frozenset[str]] = {}
    for fdef in cdef.body:
        if not (isinstance(fdef, ast.FunctionDef) and fdef.name == "__init__"):
            continue
        for node in ast.walk(fdef):
            if not (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.value, ast.Call)
            ):
                continue
            attr = _self_attr(node.targets[0])
            if attr is None:
                continue
            call = node.value
            if _dotted(call.func) not in ("jax.jit", "jit") or not call.args:
                continue
            positions = _donate_positions(call)
            resolved = resolver.resolve(call.args[0])
            if positions is None or resolved is None:
                continue
            params, _entry = resolved
            names = frozenset(
                params[p] for p in positions if p < len(params)
            )
            if names:
                out[attr] = names
    return out


def _check_use_after_donate(
    tree: ast.AST, filename: str, resolver: _ImportResolver
) -> list[Violation]:
    out: list[Violation] = []
    for cdef in (n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)):
        donating = _donating_attrs_of_class(cdef, resolver)
        if not donating:
            continue
        for fdef in cdef.body:
            if not isinstance(fdef, ast.FunctionDef) or fdef.name == "__init__":
                continue
            out.extend(
                _scan_method_for_use_after_donate(
                    fdef, donating, filename
                )
            )
    return out


def _scan_method_for_use_after_donate(
    fdef: ast.FunctionDef,
    donating: dict[str, frozenset[str]],
    filename: str,
) -> list[Violation]:
    out: list[Violation] = []
    # local aliases of donating dispatchers: fn = self._x / IfExp / partial
    local_fns: dict[str, frozenset[str]] = {}
    # list vars whose elements we can enumerate (args = [...]; args.append)
    list_vars: dict[str, list[ast.expr]] = {}

    def donated_params_of(expr: ast.expr) -> frozenset[str] | None:
        attr = _self_attr(expr)
        if attr is not None:
            return donating.get(attr)
        if isinstance(expr, ast.Name):
            return local_fns.get(expr.id)
        if isinstance(expr, ast.IfExp):
            a = donated_params_of(expr.body)
            b = donated_params_of(expr.orelse)
            if a is None and b is None:
                return None
            return (a or frozenset()) | (b or frozenset())
        if isinstance(expr, ast.Call):
            dn = _dotted(expr.func)
            if dn in ("functools.partial", "partial") and expr.args:
                return donated_params_of(expr.args[0])
        return None

    def arg_state_attrs(call: ast.Call) -> set[str]:
        found: set[str] = set()
        exprs: list[ast.expr] = []
        for a in call.args:
            if isinstance(a, ast.Starred) and isinstance(a.value, ast.Name):
                exprs.extend(list_vars.get(a.value.id, []))
            else:
                exprs.append(a)
        exprs.extend(kw.value for kw in call.keywords)
        for e in exprs:
            attr = _self_attr(e)
            if attr is not None:
                found.add(attr)
        return found

    def stmt_donating_calls(stmt: ast.stmt) -> list[tuple[ast.Call, frozenset[str]]]:
        calls = []
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                dp = donated_params_of(node.func)
                if dp:
                    calls.append((node, dp))
        return calls

    def assigned_self_attrs(stmt: ast.stmt) -> set[str]:
        attrs: set[str] = set()
        targets: list[ast.expr] = []
        if isinstance(stmt, ast.Assign):
            targets = list(stmt.targets)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            targets = [stmt.target]
        flat: list[ast.expr] = []
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                flat.extend(t.elts)
            else:
                flat.append(t)
        for t in flat:
            a = _self_attr(t)
            if a is not None:
                attrs.add(a)
        return attrs

    def track_locals(stmt: ast.stmt) -> None:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
            tgt = stmt.targets[0]
            if isinstance(tgt, ast.Name):
                dp = donated_params_of(stmt.value)
                if dp:
                    local_fns[tgt.id] = dp
                if isinstance(stmt.value, ast.List):
                    list_vars[tgt.id] = list(stmt.value.elts)
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
            call = stmt.value
            dn = _dotted(call.func)
            if dn is not None and dn.endswith(".append"):
                base = dn.rsplit(".", 1)[0]
                if base in list_vars and len(call.args) == 1:
                    list_vars[base].append(call.args[0])

    def process(stmts: Iterable[ast.stmt], dead: set[str]) -> set[str]:
        for stmt in stmts:
            if isinstance(stmt, ast.If):
                d1 = process(stmt.body, set(dead))
                d2 = process(stmt.orelse, set(dead))
                dead = d1 | d2
                continue
            if isinstance(stmt, (ast.For, ast.While)):
                dead |= process(stmt.body, set(dead))
                dead |= process(stmt.orelse, set(dead))
                continue
            if isinstance(stmt, (ast.With, ast.Try)):
                dead = process(getattr(stmt, "body", []), dead)
                for h in getattr(stmt, "handlers", []):
                    dead |= process(h.body, set(dead))
                continue
            track_locals(stmt)
            dcalls = stmt_donating_calls(stmt)
            if not dcalls:
                # plain statement: any read of a dead attr is a
                # use-after-donate
                for node in ast.walk(stmt):
                    attr = _self_attr(node)
                    if (
                        attr in dead
                        and isinstance(node.ctx, ast.Load)  # type: ignore[attr-defined]
                    ):
                        out.append(
                            Violation(
                                "DONATE-USE", filename, node.lineno,
                                f"{fdef.name}: reads self.{attr} after it "
                                f"was donated to a dispatch and before "
                                f"reassignment",
                            )
                        )
                        dead.discard(attr)  # report once
            else:
                for call, dparams in dcalls:
                    dead |= arg_state_attrs(call) & dparams
            dead -= assigned_self_attrs(stmt)
        return dead

    process(fdef.body, set())
    return out


# ---------------------------------------------------------------------------
# Check family 3b: fabric replicated-scalar order
# ---------------------------------------------------------------------------
def check_fabric_source(src: str, filename: str) -> list[Violation]:
    tree = ast.parse(src, filename=filename)
    out: list[Violation] = []
    for fdef in _funcdefs(tree):
        if fdef.name != "local":
            continue
        want = FABRIC_REPLICATED_SCALARS
        names = [a.arg for a in fdef.args.args[: len(want)]]
        got = tuple(_scalar_class_of_name(p) for p in names)
        if got != want:
            out.append(
                Violation(
                    "PREFETCH-ORDER", filename, fdef.lineno,
                    f"shard_map body `local`: leading replicated scalars "
                    f"{got} != declared {want}",
                )
            )
    return out


# ---------------------------------------------------------------------------
# Check family 2: oracle-parity registry
# ---------------------------------------------------------------------------
def _positional_params(fn: Callable[..., Any]) -> list[inspect.Parameter]:
    return [
        p
        for p in inspect.signature(fn).parameters.values()
        if p.kind
        not in (inspect.Parameter.VAR_POSITIONAL, inspect.Parameter.VAR_KEYWORD)
    ]


def _srcinfo(fn: Callable[..., Any], root: str | None) -> tuple[str, int]:
    try:
        f = inspect.getsourcefile(fn) or "<unknown>"
        line = inspect.getsourcelines(fn)[1]
    except (OSError, TypeError):
        return "<unknown>", 0
    if root:
        try:
            f = os.path.relpath(f, root)
        except ValueError:
            pass
    return f, line


def signature_violations(
    entry: ContractEntry, root: str | None = None
) -> list[Violation]:
    """Compare a registered wrapper against its oracle (names, order,
    keyword defaults), modulo the entry's declared extras."""
    file, line = _srcinfo(entry.fn, root)
    out: list[Violation] = []
    wparams = _positional_params(entry.fn)
    wnames = {p.name for p in wparams}
    for x in entry.extra:
        if x not in wnames:
            out.append(
                Violation(
                    "ORACLE-PARITY", file, line,
                    f"{entry.name}: declared extra param `{x}` does not "
                    f"exist on the wrapper (stale registration)",
                )
            )
    if entry.oracle is None:
        if not entry.reason:
            out.append(
                Violation(
                    "ORACLE-PARITY", file, line,
                    f"{entry.name}: registered without an oracle and "
                    f"without a reason",
                )
            )
        return out
    oparams = _positional_params(entry.oracle)
    onames = {p.name for p in oparams}
    for x in entry.oracle_extra:
        if x not in onames:
            out.append(
                Violation(
                    "ORACLE-PARITY", file, line,
                    f"{entry.name}: declared oracle_extra param `{x}` does "
                    f"not exist on the oracle (stale registration)",
                )
            )
    ws = [p for p in wparams if p.name not in entry.extra]
    os_ = [p for p in oparams if p.name not in entry.oracle_extra]
    oracle_name = getattr(entry.oracle, "__name__", "<oracle>")
    if entry.strict_order:
        if [p.name for p in ws] != [p.name for p in os_]:
            out.append(
                Violation(
                    "ORACLE-PARITY", file, line,
                    f"{entry.name}: wrapper params "
                    f"{[p.name for p in ws]} != oracle {oracle_name} "
                    f"params {[p.name for p in os_]} (modulo declared "
                    f"extras)",
                )
            )
            return out
        pairs = list(zip(ws, os_, strict=True))
    else:
        if {p.name for p in ws} != {p.name for p in os_}:
            out.append(
                Violation(
                    "ORACLE-PARITY", file, line,
                    f"{entry.name}: shared param name sets differ from "
                    f"oracle {oracle_name}: "
                    f"{sorted(p.name for p in ws)} vs "
                    f"{sorted(p.name for p in os_)}",
                )
            )
            return out
        by_name = {p.name: p for p in os_}
        pairs = [(p, by_name[p.name]) for p in ws]
    for wp, op in pairs:
        wd, od = wp.default, op.default
        if (wd is inspect.Parameter.empty) != (od is inspect.Parameter.empty):
            out.append(
                Violation(
                    "ORACLE-PARITY", file, line,
                    f"{entry.name}: param `{wp.name}` required on one side "
                    f"but defaulted on the other",
                )
            )
        elif wd is not inspect.Parameter.empty and wd != od:
            out.append(
                Violation(
                    "ORACLE-PARITY", file, line,
                    f"{entry.name}: param `{wp.name}` default {wd!r} != "
                    f"oracle default {od!r}",
                )
            )
    return out


_OPS_MODULE = "repro.kernels.ops"


def _load_ops_registry() -> Any:
    return importlib.import_module(_OPS_MODULE)


def check_registry(root: str) -> list[Violation]:
    """Parity for every registered wrapper + exhaustiveness over the
    public surface of ``kernels/ops.py``."""
    out: list[Violation] = []
    _load_ops_registry()
    ops_path = os.path.join(root, "src", "repro", "kernels", "ops.py")
    rel = os.path.relpath(ops_path, root)
    with open(ops_path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=rel)
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) and not node.name.startswith("_"):
            if node.name not in CONTRACT_REGISTRY:
                out.append(
                    Violation(
                        "ORACLE-MISSING", rel, node.lineno,
                        f"public kernel entry `{node.name}` has no "
                        f"@dataplane_contract registration",
                    )
                )
    for entry in CONTRACT_REGISTRY.values():
        out.extend(signature_violations(entry, root))
    return out


# ---------------------------------------------------------------------------
# Check family 5: host-mirror pairing lint
# ---------------------------------------------------------------------------
def _terminal_attr(node: ast.expr) -> tuple[str, int] | None:
    """Attribute name + line for stores through ``x.attr`` or
    ``x.attr[...]`` target shapes (any base object, so ``self.hw._x``
    and ``self.x[gid]`` both match)."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute):
        return node.attr, node.lineno
    return None


def check_mirror_source(src: str, filename: str) -> list[Violation]:
    tree = ast.parse(src, filename=filename)
    out: list[Violation] = []
    for cdef in (n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)):
        for fdef in cdef.body:
            if not isinstance(fdef, ast.FunctionDef):
                continue
            guarded = fdef.name == "__init__" or any(
                (_dotted(d) or "").split(".")[-1] == "mirror_guard"
                for d in fdef.decorator_list
            )
            if guarded:
                continue
            for node in ast.walk(fdef):
                targets: list[ast.expr] = []
                if isinstance(node, ast.Assign):
                    targets = list(node.targets)
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                for t in targets:
                    flat = (
                        list(t.elts)
                        if isinstance(t, (ast.Tuple, ast.List))
                        else [t]
                    )
                    for leaf in flat:
                        hit = _terminal_attr(leaf)
                        if hit is not None and hit[0] in MIRROR_ATTRS:
                            out.append(
                                Violation(
                                    "MIRROR-GUARD", filename, hit[1],
                                    f"{cdef.name}.{fdef.name} mutates host "
                                    f"mirror `{hit[0]}` outside a "
                                    f"@mirror_guard-annotated method",
                                )
                            )
    return out


# ---------------------------------------------------------------------------
# Repo driver
# ---------------------------------------------------------------------------
def _default_root() -> str:
    # src/repro/analysis/contracts.py -> repo root
    here = os.path.abspath(os.path.dirname(__file__))
    return os.path.abspath(os.path.join(here, "..", "..", ".."))


def _read(root: str, rel: str) -> str:
    with open(os.path.join(root, rel), encoding="utf-8") as f:
        return f.read()


def _ensure_importable(root: str) -> None:
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def pallas_sites(root: str | None = None) -> list[PallasSite]:
    """Every audited ``pallas_call`` site under ``src/repro/kernels`` —
    the exhaustiveness surface (tests pin the wirepath.py subset)."""
    root = root or _default_root()
    sites: list[PallasSite] = []
    kdir = os.path.join(root, "src", "repro", "kernels")
    for name in sorted(os.listdir(kdir)):
        if not name.endswith(".py"):
            continue
        rel = os.path.join("src", "repro", "kernels", name)
        _, s = check_kernel_source(_read(root, rel), rel)
        sites.extend(s)
    return sites


def check_repo(root: str | None = None) -> list[Violation]:
    """Run every contract family over the repository."""
    root = root or _default_root()
    _ensure_importable(root)
    out: list[Violation] = []

    kdir = os.path.join(root, "src", "repro", "kernels")
    for name in sorted(os.listdir(kdir)):
        if not name.endswith(".py"):
            continue
        rel = os.path.join("src", "repro", "kernels", name)
        v, _sites = check_kernel_source(_read(root, rel), rel)
        out.extend(v)

    for tail in DONATION_FILES:
        rel = os.path.join("src", "repro", tail)
        out.extend(check_dispatch_source(_read(root, rel), rel))

    rel = os.path.join("src", "repro", "core", "fabric.py")
    out.extend(check_fabric_source(_read(root, rel), rel))

    rel = os.path.join("src", "repro", "core", "api.py")
    out.extend(check_mirror_source(_read(root, rel), rel))

    out.extend(check_registry(root))
    return sorted(out, key=lambda v: (v.file, v.line, v.rule))


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.analysis.contracts",
        description="Static dataplane contract checker (DESIGN.md §12).",
    )
    ap.add_argument(
        "--root", default=None, help="repository root (default: inferred)"
    )
    ap.add_argument(
        "--strict-advisory",
        action="store_true",
        help="treat advisory findings as errors",
    )
    ns = ap.parse_args(argv)
    violations = check_repo(ns.root)
    errors = 0
    for v in violations:
        print(v, file=sys.stderr)
        if not v.advisory or ns.strict_advisory:
            errors += 1
    if errors:
        print(
            f"contracts: {errors} violation(s) "
            f"({len(violations) - errors} advisory)",
            file=sys.stderr,
        )
        return 1
    n_sites = len(pallas_sites(ns.root))
    print(
        f"contracts OK: {len(CONTRACT_REGISTRY)} registered kernel entries, "
        f"{n_sites} pallas_call sites audited"
        + (f", {len(violations)} advisory note(s)" if violations else "")
    )
    return 0


if __name__ == "__main__":
    # ``python -m`` executes this file as ``__main__``; delegate to the
    # canonical module instance so the registry populated by importing
    # ``repro.kernels.ops`` is the one we read.
    from repro.analysis.contracts import main as _main

    sys.exit(_main())
