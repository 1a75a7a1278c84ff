"""Canonical jaxpr normalization and structural fingerprints.

The graph-contract layer (analysis/graph_diff.py) needs to answer two
questions about a traced program without compiling or running it:

* "is this the SAME program we blessed last time?" — drift detection
  against golden fingerprints checked into ``analysis/golden/``;
* "how does variant B differ from baseline A, primitive by primitive?" —
  the differential equivalence prover's raw material.

Both reduce to a *canonical form* of the jaxpr: variables alpha-renamed in
first-use order (trace-time ``Var.count`` values are process-global and
differ run to run), equations rendered in their (deterministic) trace
order, sub-jaxprs (pjit/scan/while/cond/custom_*/pallas_call bodies)
inlined depth-first each with a fresh naming scope, and equation params
reduced to a stable value rendering that never leaks object identities
(function addresses, mesh device ids). The canonical form hashes to the
**structural fingerprint**; alongside the hash ride the primitive /
dot-dtype / collective / gather-scatter histograms, so a fingerprint
mismatch can always be explained as a readable ±primitive diff instead of
just "hash changed".

This module also owns the jaxpr *walking* helpers the rest of the analysis
layer builds on (``iter_eqns`` and friends) — ``analysis/graph_audit.py``
re-exports them for its callers.

Everything here is pure structure inspection: no compilation, no
execution, no device transfers.
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import Counter

import numpy as np

from jax.extend.core import ClosedJaxpr, Jaxpr


#: primitive names that are explicit cross-device collectives
COLLECTIVE_PRIMS = frozenset(
    {
        "psum",
        "pmax",
        "pmin",
        "all_gather",
        "all_to_all",
        "ppermute",
        "pshuffle",
        "reduce_scatter",
        "psum_scatter",
    }
)

#: primitive names that materialize indexed reads/writes — the "did the
#: paged layout add exactly the declared page-table movement?" census
GATHER_SCATTER_PRIMS = frozenset(
    {
        "gather",
        "scatter",
        "scatter-add",
        "scatter_add",
        "dynamic_slice",
        "dynamic_update_slice",
    }
)


# -- jaxpr walking ----------------------------------------------------------


def _sub_jaxprs(eqn):
    """Yield every jaxpr nested in an eqn's params (pjit/scan/while/cond/
    custom_* / pallas_call bodies), each exactly once."""
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for x in vals:
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def iter_eqns(jaxpr):
    """Depth-first walk over every equation, descending into sub-jaxprs.

    Each sub-jaxpr is visited ONCE regardless of how many times it executes
    (a `lax.scan` body counts once) — the resulting census is a *structural
    fingerprint* of the program, which is exactly what a regression check
    wants: inserting one collective into a scan body changes the count by
    one, not by n_steps."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _dtype_name(dtype) -> str:
    try:
        return np.dtype(dtype).name
    except TypeError:
        return str(dtype)  # extended dtypes (PRNG keys) have no numpy twin


def collective_counts(jaxpr) -> dict:
    """Structural count of explicit collective primitives."""
    c: Counter = Counter()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            c[name] += 1
    return dict(c)


def dtype_census(jaxpr) -> set:
    """Set of dtypes appearing on any equation output."""
    out = set()
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                out.add(_dtype_name(aval.dtype))
    return out


def dot_input_census(jaxpr) -> Counter:
    """Counter of (lhs_dtype, rhs_dtype) pairs over every dot_general."""
    c: Counter = Counter()
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        c[(_dtype_name(lhs.dtype), _dtype_name(rhs.dtype))] += 1
    return c


def primitive_counts(jaxpr) -> dict:
    """Structural count of EVERY primitive (the full histogram)."""
    c: Counter = Counter()
    for eqn in iter_eqns(jaxpr):
        c[eqn.primitive.name] += 1
    return dict(c)


def pool_gather_count(jaxpr, pool_shape) -> int:
    """Count of `gather` equations whose operand IS the KV pool (an invar
    of exactly `pool_shape`) — the materialized-page-view reads the fused
    int8 decode kernel exists to eliminate (scalar-prefetch page tables,
    ops/pallas_attention.py). The float paged twin legitimately carries
    them; the int8 decode contract pins them to zero."""
    shape = tuple(pool_shape)
    n = 0
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "gather":
            continue
        if any(
            tuple(getattr(v.aval, "shape", ())) == shape for v in eqn.invars
        ):
            n += 1
    return n


# -- canonical form ---------------------------------------------------------


def _aval_str(aval) -> str:
    dt = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dt is None:
        return type(aval).__name__
    dims = "" if shape is None else ",".join(str(d) for d in shape)
    return f"{_dtype_name(dt)}[{dims}]"


def _canon_param(v) -> str:
    """Stable rendering of one equation param value: literals verbatim,
    containers recursively, dtypes by name, jaxprs as a placeholder (their
    bodies are normalized inline by `normalize`), everything else by TYPE
    name only — a function object, a sharding carrying mesh device ids, or
    any repr with a memory address must never reach the hash."""
    if isinstance(v, (ClosedJaxpr, Jaxpr)):
        return "<jaxpr>"
    if v is None or isinstance(v, (bool, int, float, complex, str)):
        return repr(v)
    if isinstance(v, (list, tuple)):
        return "(" + ",".join(_canon_param(x) for x in v) + ")"
    if isinstance(v, dict):
        return (
            "{"
            + ",".join(
                f"{k}:{_canon_param(v[k])}" for k in sorted(v, key=str)
            )
            + "}"
        )
    if isinstance(v, np.ndarray):
        return f"ndarray:{_dtype_name(v.dtype)}{tuple(v.shape)}"
    try:
        return f"dtype:{np.dtype(v).name}"
    except TypeError:
        pass
    if callable(v):
        return f"fn:{getattr(v, '__name__', type(v).__name__)}"
    return type(v).__name__


def normalize(jaxpr) -> list:
    """The canonical (alpha-renamed, stably-ordered) line rendering of a
    jaxpr: one line per equation, `o0:f32[2,8] = prim[k=v] i1 i2`, with
    sub-jaxprs inlined depth-first (indented, fresh variable scope per
    sub-jaxpr, visited in sorted-param-key order so the walk itself is
    deterministic). Two traces of the same program normalize identically
    regardless of trace-time Var counters; any structural change — an
    extra primitive, a changed dtype, a reordered operand — changes at
    least one line."""
    lines: list = []

    def render(jx, indent):
        if isinstance(jx, ClosedJaxpr):
            jx = jx.jaxpr
        names: dict = {}

        def name(v):
            val = getattr(v, "val", None)
            if val is not None or type(v).__name__ == "Literal":
                # literal operand: the value is part of the structure (a
                # changed constant IS graph drift); arrays render by shape
                if isinstance(val, np.ndarray) and val.size > 8:
                    return f"lit:{_dtype_name(val.dtype)}{tuple(val.shape)}"
                return f"lit:{val!r}"
            if v not in names:
                names[v] = f"v{len(names)}"
            return names[v]

        pad = "  " * indent
        for v in list(jx.constvars) + list(jx.invars):
            name(v)
        lines.append(
            pad
            + "in: "
            + " ".join(
                f"{name(v)}:{_aval_str(v.aval)}"
                for v in list(jx.constvars) + list(jx.invars)
            )
        )
        for eqn in jx.eqns:
            params = ",".join(
                f"{k}={_canon_param(eqn.params[k])}"
                for k in sorted(eqn.params, key=str)
            )
            outs = " ".join(
                f"{name(v)}:{_aval_str(v.aval)}" for v in eqn.outvars
            )
            ins = " ".join(name(v) for v in eqn.invars)
            lines.append(
                f"{pad}{outs} = {eqn.primitive.name}[{params}] {ins}"
            )
            for k in sorted(eqn.params, key=str):
                v = eqn.params[k]
                vals = v if isinstance(v, (list, tuple)) else (v,)
                for x in vals:
                    if isinstance(x, (ClosedJaxpr, Jaxpr)):
                        render(x, indent + 1)
        lines.append(pad + "out: " + " ".join(name(v) for v in jx.outvars))

    render(jaxpr, 0)
    return lines


def structural_hash(jaxpr) -> str:
    """sha256 of the canonical form — THE program identity the golden
    ladder pins."""
    text = "\n".join(normalize(jaxpr))
    return hashlib.sha256(text.encode()).hexdigest()


# -- fingerprints -----------------------------------------------------------


@dataclasses.dataclass
class Fingerprint:
    """One program's structural identity: the canonical-form hash plus the
    histograms that make a mismatch explainable (and diffable) at the
    primitive level."""

    hash: str
    n_eqns: int
    primitives: dict  # primitive name -> count
    dots: dict  # "lhs_dtype x rhs_dtype" -> count
    collectives: dict  # collective primitive -> count
    gathers: dict  # gather/scatter-family primitive -> count

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Fingerprint":
        return cls(
            hash=d["hash"],
            n_eqns=d["n_eqns"],
            primitives=dict(d.get("primitives", {})),
            dots=dict(d.get("dots", {})),
            collectives=dict(d.get("collectives", {})),
            gathers=dict(d.get("gathers", {})),
        )


def fingerprint(jaxpr) -> Fingerprint:
    prims = primitive_counts(jaxpr)
    return Fingerprint(
        hash=structural_hash(jaxpr),
        n_eqns=sum(prims.values()),
        primitives=prims,
        dots={
            f"{l} x {r}": n for (l, r), n in sorted(dot_input_census(jaxpr).items())
        },
        collectives=collective_counts(jaxpr),
        gathers={
            k: v
            for k, v in sorted(prims.items())
            if k in GATHER_SCATTER_PRIMS
        },
    )


def primitive_delta(a: Fingerprint, b: Fingerprint):
    """(added, removed) primitive Counters going a -> b: what the variant
    introduced and what it dropped, structurally."""
    ca, cb = Counter(a.primitives), Counter(b.primitives)
    added = Counter({k: v for k, v in (cb - ca).items() if v})
    removed = Counter({k: v for k, v in (ca - cb).items() if v})
    return added, removed


def diff_fingerprints(a: Fingerprint, b: Fingerprint) -> list:
    """Readable primitive-level diff between two fingerprints (empty when
    the structural hashes match). Lines name each drifted primitive with
    its count delta — the artifact a CI failure prints."""
    if a.hash == b.hash:
        return []
    lines = []
    added, removed = primitive_delta(a, b)
    for name in sorted(added):
        lines.append(f"+{name} x{added[name]}")
    for name in sorted(removed):
        lines.append(f"-{name} x{removed[name]}")
    for key in sorted(set(a.dots) | set(b.dots)):
        na, nb = a.dots.get(key, 0), b.dots.get(key, 0)
        if na != nb:
            lines.append(f"dot_general({key}): {na} -> {nb}")
    for key in sorted(set(a.collectives) | set(b.collectives)):
        na, nb = a.collectives.get(key, 0), b.collectives.get(key, 0)
        if na != nb:
            lines.append(f"collective {key}: {na} -> {nb}")
    if not lines:
        lines.append(
            "identical primitive census — structural reordering, a shape/"
            "dtype change, or an equation-param change (same op multiset)"
        )
    return lines
