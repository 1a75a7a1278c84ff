"""Golden-ladder drift detection and the differential equivalence prover.

Two static gates on top of the canonical fingerprints
(analysis/jaxpr_tools.py) and the declared contracts
(analysis/graph_audit.py):

* **Golden fingerprints** — every warm-ladder program's canonical hash +
  primitive histograms are checked into ``analysis/golden/<config>.json``.
  ``--check`` re-traces the ladder and fails on ANY structural drift with
  a readable ±primitive diff; ``--bless`` re-writes the goldens after an
  *intentional* graph change (the diff goes in the PR for review). The
  coverage gate additionally proves every ``engine.warm_plan()`` entry
  carries both a declared contract and a golden fingerprint — a new
  program kind cannot land unaudited.

* **Differential equivalence prover** — the engine's variant axes are
  *declared transformations* of a baseline, and the prover asserts each
  variant's normalized diff is exactly the declared delta:

  - paged = contiguous + {page-table gather + remapped scatter writes}
    (runtime/paged_kv.py) — and NOTHING else: no new collective, no new
    dot, no undeclared primitive;
  - int8 = f32 + {convert_element_type, scale mul/div, the scale
    sidecars' page gathers} (ops/kv_quant.py, PR 17) — with zero gathers
    of the pool where the page-table decode kernel is active, on both sides;
  - verify_k = prefill twin of the same shape + {argmax fusion}
    (runtime/speculative.py) — same collectives, same dot census;
  - masked = unmasked + {mask-table gathers + comparison/where selects}
    (runtime/grammar.py, PR 20) — grammar-constrained decoding may add
    ONLY the [S, V] table lookups and the select that pins illegal
    logits: no new dot, no new collective, and the prefill family
    (which never samples) must be bit-identical.

  Any undeclared primitive, extra collective, changed dot-dtype census,
  reintroduced pool gather, or lost cache donation fails with a diff
  naming the offending primitive.

Everything here is `jax.make_jaxpr` / `.lower()` only — no compilation,
no execution. CLI: ``python -m distributed_llama_tpu.analysis.graph_diff``
(or ``scripts/dlt_graph_diff.py``).
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax

from . import graph_audit as ga
from .jaxpr_tools import (
    Fingerprint,
    diff_fingerprints,
    fingerprint,
    pool_gather_count,
    primitive_delta,
)

#: where blessed goldens live, keyed by config_key(engine)
GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")

GOLDEN_VERSION = 1


class GraphDiffError(AssertionError):
    """Golden drift, coverage hole, or a failed equivalence proof."""


# -- golden store -----------------------------------------------------------


def entry_key(entry) -> str:
    """The stable per-program key: ``kind[size|kvN]`` — same rendering the
    audit reports and the engine's watchdog labels use."""
    return f"{entry.kind}[{entry.size}|kv{entry.kv_len}]"


def config_key(engine) -> str:
    """One golden file per distinct program-shaping configuration: layout,
    stored-KV dtype, compute dtype, batch/chunk geometry, speculative and
    prefix ladders, and mesh topology all change the traced graphs."""
    import numpy as np

    cfg = engine.cfg
    layout = "paged" if getattr(engine, "paged", False) else "contig"
    kv = np.dtype(engine.cache.k.dtype).name
    compute = np.dtype(cfg.dtype).name
    spec = f"spec{engine.draft_k}" if engine.spec_mode else "nospec"
    pfx = (
        f"pfx{len(engine.prefix_cache.buckets)}"
        if engine.prefix_cache is not None and engine.prefix_cache.buckets
        else "nopfx"
    )
    mesh = "nomesh"
    if engine.mesh is not None:
        mesh = "-".join(
            f"{ax}{n}" for ax, n in engine.mesh.shape.items() if n > 1
        ) or "mesh1"
    # interpret-mode pallas changes WHICH kernels trace (the page-table
    # decode kernel becomes CPU-eligible) — a different program family,
    # hence a different golden file
    pi = "_pi" if getattr(cfg, "pallas_interpret", False) else ""
    # a grammar arena threads the [S, V] mask-table + state operands into
    # every decode/verify program (runtime/grammar.py) — a different
    # program family, keyed by the arena's state capacity (the table
    # operand's shape, hence part of every masked fingerprint)
    gr = ""
    if getattr(engine, "grammar", None) is not None:
        gr = f"_gr{engine.grammar.n_states}"
    # a layer pattern changes every program's body (a period of layers, a
    # second kind of cache; latent attention and held experts): its own
    # family, keyed by the architecture
    arch = ""
    if cfg.is_hybrid or cfg.is_latent or cfg.window:
        from ..formats.mfile import ArchType

        arch = "_" + ArchType.name(cfg.arch_type)
    return (
        f"{layout}_{kv}_{compute}_b{engine.batch}"
        f"_c{engine.max_chunk}_d{engine.decode_chunk_size}"
        f"_{spec}_{pfx}_{mesh}{pi}{gr}{arch}"
    )


def golden_path(golden_dir: str, key: str) -> str:
    return os.path.join(golden_dir, key + ".json")


def fingerprint_ladder(engine, ladder=None) -> dict:
    """entry_key -> Fingerprint for every warm-ladder program."""
    ladder = ga.warm_key_ladder(engine) if ladder is None else ladder
    return {
        entry_key(e): fingerprint(ga.trace_entry(engine, e)) for e in ladder
    }


def load_golden(golden_dir: str, key: str) -> dict | None:
    """{entry_key: Fingerprint} from the blessed file, or None when this
    config was never blessed."""
    path = golden_path(golden_dir, key)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        doc = json.load(f)
    return {
        k: Fingerprint.from_dict(d) for k, d in doc["programs"].items()
    }


def bless(engine, golden_dir: str = GOLDEN_DIR) -> str:
    """Re-trace the full warm ladder and write its fingerprints as the new
    goldens for this config. The resulting file diff IS the reviewable
    artifact of an intentional graph change."""
    key = config_key(engine)
    prints = fingerprint_ladder(engine)
    doc = {
        "version": GOLDEN_VERSION,
        "config": key,
        "jax": jax.__version__,
        "programs": {k: fp.to_dict() for k, fp in sorted(prints.items())},
    }
    os.makedirs(golden_dir, exist_ok=True)
    path = golden_path(golden_dir, key)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def check_fingerprints(engine, golden_dir: str = GOLDEN_DIR) -> list:
    """Re-trace the warm ladder and diff against the blessed goldens.
    Returns problem strings — empty means zero structural drift. Every
    drifted program reports a primitive-level diff, never just a hash."""
    key = config_key(engine)
    golden = load_golden(golden_dir, key)
    if golden is None:
        return [
            f"no golden fingerprints for config {key!r} — bless this "
            "config first (scripts/dlt_graph_diff.py --bless)"
        ]
    problems = []
    current = fingerprint_ladder(engine)
    for ek in sorted(current):
        fp = current[ek]
        want = golden.get(ek)
        if want is None:
            problems.append(
                f"{ek}: program is on warm_plan() but has no golden "
                "fingerprint — an unreviewed ladder growth; re-bless "
                "after review"
            )
            continue
        if fp.hash != want.hash:
            diff = diff_fingerprints(want, fp)
            problems.append(
                f"{ek}: structural drift from blessed golden "
                f"({want.hash[:12]} -> {fp.hash[:12]}):\n      "
                + "\n      ".join(diff)
            )
    for ek in sorted(set(golden) - set(current)):
        problems.append(
            f"{ek}: golden fingerprint is stale — program left "
            "warm_plan(); re-bless after review"
        )
    return problems


def coverage_problems(engine, golden_dir: str = GOLDEN_DIR) -> list:
    """The 100%-coverage gate: every warm_plan() entry must carry BOTH a
    declared contract (graph_audit.contract_for) and a blessed golden
    fingerprint. Cheap — no tracing, just registry + file lookups."""
    problems = []
    golden = load_golden(golden_dir, config_key(engine)) or {}
    for entry in ga.warm_key_ladder(engine):
        ek = entry_key(entry)
        try:
            ga.contract_for(engine, entry)
        except ga.GraphAuditError as e:
            problems.append(f"{ek}: no declared contract — {e}")
        if ek not in golden:
            problems.append(
                f"{ek}: no golden fingerprint for config "
                f"{config_key(engine)!r} — bless it "
                "(scripts/dlt_graph_diff.py --bless)"
            )
    return problems


# -- declared transformation specs ------------------------------------------


@dataclasses.dataclass(frozen=True)
class TransformSpec:
    """ONE declared graph transformation between a baseline and a variant
    program. The prover admits exactly the declared delta: every primitive
    the variant adds must be in `allowed_added`, every primitive it drops
    in `allowed_removed`, and (unless waived) the collective multiset and
    the dot-dtype census must be IDENTICAL — a variant axis is never
    allowed to change what runs on the MXU or crosses the interconnect.

    `pin_pool_gathers`: the int8 clause — the variant must trace ZERO
    gathers of its KV pool wherever its contract pins them (the fused
    page-table-aware decode kernel, PR 17), and must never trace more
    pool gathers than the baseline anywhere else. Exactly there the dot
    census may grow, by exactly one f32 qk^T/pV pair per kv head beyond
    the first: the kernel loops the heads the baseline's two einsums batch
    (the TPU's compiler takes whole-page blocks only).
    """

    name: str
    allowed_added: frozenset
    allowed_removed: frozenset
    require_equal_collectives: bool = True
    require_equal_dots: bool = True
    pin_pool_gathers: bool = False


#: paged = contiguous + the page-table indirection: gathers of K/V pages
#: steered by the [b, slots] table, scatter writes remapped through it,
#: and the slot arithmetic (div/rem by page_size, bounds selects) that
#: computes page ids — in exchange for the contiguous layout's
#: dynamic_slice/dynamic_update_slice window movement (and, on the
#: admission prefill, the row-slice masking add/select_n arithmetic the
#: page table obviates: paged admission rides the plain b=1 forward).
PAGED_VS_CONTIGUOUS = TransformSpec(
    name="paged-vs-contiguous",
    allowed_added=frozenset(
        {
            "gather", "scatter", "concatenate", "reshape", "iota",
            "broadcast_in_dim", "convert_element_type", "jit",
            "add", "sub", "mul", "div", "rem", "sign",
            "lt", "le", "ge", "eq", "ne", "and", "or", "min", "max",
            "select_n",
        }
    ),
    allowed_removed=frozenset(
        {
            "dynamic_slice", "dynamic_update_slice", "squeeze", "slice",
            "add", "select_n",
        }
    ),
)

#: int8 = f32 + the quantization arithmetic (convert_element_type, scale
#: mul/div, abs/round/reduce_max for requantization) and what the page-table
#: decode kernel does for an int8 pool beside what it does for a float one
#: (both take it wherever one does): the scale sidecars' pages gathered in
#: HLO (gather, reshape, pad to whole blocks), their two operands' reads
#: and the column multiplies in the body. The pool itself is never
#: gathered where the decode contract pins it, and never more than in f32.
INT8_VS_F32 = TransformSpec(
    name="int8-vs-f32",
    allowed_added=frozenset(
        {
            "convert_element_type", "mul", "div", "add", "sub",
            "abs", "round", "reduce_max", "max", "min", "sign", "exp",
            "lt", "le", "eq", "ne", "and", "select_n",
            "reshape", "broadcast_in_dim", "iota", "concatenate",
            "slice", "squeeze", "rem", "scatter", "jit",
            "pallas_call", "program_id", "get", "swap", "cond",
            "gather", "pad",
        }
    ),
    allowed_removed=frozenset({"gather", "stop_gradient"}),
    pin_pool_gathers=True,
)

#: verify_k = a prefill twin of the same (size, kv) shape + the in-graph
#: argmax fusion over every drafted position — minus the last-position
#: slice extraction the prefill-shaped program does instead. Collectives
#: and dot census identical (the ISSUE-5 "verify rides prefill" contract).
VERIFY_VS_PREFILL = TransformSpec(
    name="verify-vs-prefill",
    allowed_added=frozenset(
        {"argmax", "reshape", "broadcast_in_dim", "iota", "concatenate",
         "scatter"}
    ),
    allowed_removed=frozenset(
        {"add", "lt", "select_n", "dynamic_slice", "dynamic_update_slice",
         "squeeze"}
    ),
)

#: masked = unmasked + the grammar constraint machinery, per decode step:
#: the mask lookup (table[state] gather -> `>= 0` legality -> select_n
#: pinning illegal logits to -inf) and the in-graph DFA advance
#: (table[state, tok] gather -> `< 0` free-row guard -> select_n), plus
#: the scan-carry plumbing (broadcast/concatenate/jit) threading the
#: state vector. NOTHING may be removed, and the dot census + collective
#: multiset are pinned — masking is pure logits post-processing; an MXU
#: or interconnect delta would mean the mask leaked into the forward.
MASKED_VS_UNMASKED = TransformSpec(
    name="masked-vs-unmasked",
    allowed_added=frozenset(
        {
            "gather", "ge", "lt", "add", "select_n",
            "broadcast_in_dim", "concatenate", "jit",
        }
    ),
    allowed_removed=frozenset(),
)

DECLARED_SPECS = {
    "paged": PAGED_VS_CONTIGUOUS,
    "int8": INT8_VS_F32,
    "verify": VERIFY_VS_PREFILL,
    "masked": MASKED_VS_UNMASKED,
}


# -- the prover -------------------------------------------------------------


def prove_delta(
    spec: TransformSpec,
    base_fp: Fingerprint,
    variant_fp: Fingerprint,
    label: str = "",
) -> list:
    """Assert variant = base + exactly the declared delta. Every problem
    line names the offending primitive."""
    tag = f"{spec.name}{f' {label}' if label else ''}"
    problems = []
    added, removed = primitive_delta(base_fp, variant_fp)
    for name in sorted(added):
        if name not in spec.allowed_added:
            problems.append(
                f"{tag}: undeclared primitive +{name} x{added[name]} in "
                "variant — not part of the declared transformation"
            )
    for name in sorted(removed):
        if name not in spec.allowed_removed:
            problems.append(
                f"{tag}: undeclared primitive -{name} x{removed[name]} "
                "dropped by variant — not part of the declared "
                "transformation"
            )
    if spec.require_equal_collectives:
        keys = set(base_fp.collectives) | set(variant_fp.collectives)
        for name in sorted(keys):
            nb = base_fp.collectives.get(name, 0)
            nv = variant_fp.collectives.get(name, 0)
            if nb != nv:
                problems.append(
                    f"{tag}: collective {name} changed x{nb} -> x{nv} — a "
                    "variant axis must never change what crosses the "
                    "interconnect"
                )
    if spec.require_equal_dots:
        keys = set(base_fp.dots) | set(variant_fp.dots)
        for key in sorted(keys):
            nb = base_fp.dots.get(key, 0)
            nv = variant_fp.dots.get(key, 0)
            if nb != nv:
                problems.append(
                    f"{tag}: dot_general({key}) changed x{nb} -> x{nv} — a "
                    "variant axis must never change the matmul dtype census"
                )
    return problems


def _provable_entries(base_engine, variant_engine):
    """The (kind, size, kv) programs BOTH engines compile, excluding the
    layout-specific copy programs (prefix_* vs page_* — different kinds by
    construction, covered by their own contracts + goldens)."""
    keep = lambda e: not ga.KIND_REGISTRY[e.kind]["copy_program"]
    base = {entry_key(e): e for e in ga.warm_key_ladder(base_engine) if keep(e)}
    var = {entry_key(e): e for e in ga.warm_key_ladder(variant_engine) if keep(e)}
    shared = sorted(set(base) & set(var))
    only = sorted(set(base) ^ set(var))
    return [base[k] for k in shared], only


def prove_variant_pair(base_engine, variant_engine, spec: TransformSpec) -> list:
    """Prove every shared forward-shaped warm-ladder program of the
    variant engine equivalent to the baseline's modulo `spec`, plus the
    engine-wide clauses (cache donation survived, pool-gather pin)."""
    entries, unshared = _provable_entries(base_engine, variant_engine)
    problems = []
    if not entries:
        problems.append(
            f"{spec.name}: no shared warm-ladder programs to prove "
            f"(unshared: {unshared})"
        )
    for entry in entries:
        bj = ga.trace_entry(base_engine, entry)
        vj = ga.trace_entry(variant_engine, entry)
        problems += prove_delta(
            spec, fingerprint(bj), fingerprint(vj), entry_key(entry)
        )
        if spec.pin_pool_gathers:
            n_base = pool_gather_count(bj, base_engine.cache.k.shape)
            n_var = pool_gather_count(vj, variant_engine.cache.k.shape)
            contract = ga.contract_for(variant_engine, entry)
            if contract.forbid_pool_gather is not None and n_var:
                problems.append(
                    f"{spec.name} {entry_key(entry)}: gather x{n_var} "
                    "reintroduces the materialized KV-pool read the "
                    "page-table decode kernel eliminated"
                )
            elif n_var > n_base:
                problems.append(
                    f"{spec.name} {entry_key(entry)}: gather of the KV pool "
                    f"x{n_base} -> x{n_var} — the int8 transformation must "
                    "never ADD pool-materializing gathers"
                )
    # the transformation must preserve donation: a variant that silently
    # un-donates the cache doubles HBM traffic with no functional symptom
    for p in ga.donation_problems(variant_engine):
        problems.append(f"{spec.name}: {p}")
    return problems


def prove_masked_twin(base_engine, masked_engine) -> list:
    """Prove the grammar-capable engine's warm ladder equivalent to the
    grammar-less twin's modulo MASKED_VS_UNMASKED. Two clauses sharpen the
    generic variant proof: the ladder itself must be identical (masking
    adds operands to existing programs, never new programs), and the
    prefill family — which never samples — must be BIT-identical, not
    merely delta-clean."""
    spec = MASKED_VS_UNMASKED
    if getattr(masked_engine, "grammar", None) is None:
        return [
            f"{spec.name}: variant engine built no grammar arena "
            "(grammar-constrained decoding is single-chip device-decode "
            "only) — nothing to prove"
        ]
    entries, unshared = _provable_entries(base_engine, masked_engine)
    problems = []
    if unshared:
        problems.append(
            f"{spec.name}: masking changed the warm ladder itself "
            f"(unshared programs: {unshared}) — the arena must only add "
            "operands to existing programs"
        )
    for entry in entries:
        bf = fingerprint(ga.trace_entry(base_engine, entry))
        vf = fingerprint(ga.trace_entry(masked_engine, entry))
        if entry.kind in ("prefill", "prefill_row"):
            if bf.hash != vf.hash:
                problems.append(
                    f"{spec.name} {entry_key(entry)}: prefill program "
                    "changed under masking — prefill never samples, the "
                    "mask operands must not reach it:\n      "
                    + "\n      ".join(diff_fingerprints(bf, vf))
                )
            continue
        problems += prove_delta(spec, bf, vf, entry_key(entry))
    # masking must not cost the cache donation either (the masked scan
    # carries the state vector through the same donated-cache loop)
    for p in ga.donation_problems(masked_engine):
        problems.append(f"{spec.name}: {p}")
    return problems


def prove_verify_twin(engine) -> list:
    """Prove every speculative verify program equivalent to a prefill twin
    of the same (size, kv) shape, modulo VERIFY_VS_PREFILL. The twin is
    traced off-ladder — trace_entry works for any (kind, size, kv)."""
    spec = VERIFY_VS_PREFILL
    ladder = ga.warm_key_ladder(engine)
    targets = [e for e in ladder if e.kind in ("verify", "verify_row")]
    if not targets:
        return [
            f"{spec.name}: engine has no verify programs to prove "
            "(speculative off?)"
        ]
    problems = []
    for entry in targets:
        twin_kind = "prefill" if entry.kind == "verify" else "prefill_row"
        twin = ga.LadderEntry(twin_kind, entry.size, entry.kv_len)
        bj = ga.trace_entry(engine, twin)
        vj = ga.trace_entry(engine, entry)
        problems += prove_delta(
            spec, fingerprint(bj), fingerprint(vj),
            f"{entry_key(entry)} vs {entry_key(twin)}",
        )
    return problems


# -- CLI --------------------------------------------------------------------


def _clone_args(args, **overrides):
    import argparse

    d = dict(vars(args))
    d.update(overrides)
    return argparse.Namespace(**d)


def main(argv=None) -> int:
    import argparse
    import tempfile

    p = argparse.ArgumentParser(
        prog="dlt-graph-diff",
        description="golden jaxpr fingerprints + differential equivalence "
        "prover over the warm-key ladder",
    )
    ga.add_engine_args(p)
    p.add_argument(
        "--golden-dir", default=GOLDEN_DIR,
        help=f"golden fingerprint directory (default: {GOLDEN_DIR})",
    )
    p.add_argument(
        "--bless", action="store_true",
        help="re-trace the ladder and overwrite this config's goldens "
        "(the file diff is the reviewable artifact)",
    )
    p.add_argument(
        "--check", action="store_true",
        help="diff the traced ladder against the blessed goldens "
        "(default action when nothing else is asked)",
    )
    p.add_argument(
        "--coverage", action="store_true",
        help="the 100%% gate: every warm_plan() entry has a contract AND "
        "a golden",
    )
    p.add_argument(
        "--prove",
        choices=["paged", "int8", "verify", "masked", "all"], default=None,
        help="differential equivalence proof: paged-vs-contiguous, "
        "int8-vs-f32 (paged), verify-vs-prefill twins, "
        "masked-vs-unmasked (grammar), or all of them",
    )
    args = p.parse_args(argv)
    if not (args.bless or args.coverage or args.prove):
        args.check = True

    problems = []
    with tempfile.TemporaryDirectory() as d:
        engine = ga.engine_from_args(args, d)
        try:
            if args.bless:
                path = bless(engine, args.golden_dir)
                n = len(ga.warm_key_ladder(engine))
                print(f"🖋  blessed {n} program fingerprints -> {path}")
            if args.check:
                drift = check_fingerprints(engine, args.golden_dir)
                problems += drift
                print(
                    f"🔎 golden check [{config_key(engine)}]: "
                    + ("ok" if not drift else f"{len(drift)} problem(s)")
                )
            if args.coverage:
                cov = coverage_problems(engine, args.golden_dir)
                problems += cov
                print(
                    "🔎 coverage gate: "
                    + ("ok" if not cov else f"{len(cov)} hole(s)")
                )
        finally:
            engine.close()

        proofs = []
        if args.prove:
            proofs = (
                list(DECLARED_SPECS) if args.prove == "all" else [args.prove]
            )
        for mode in proofs:
            if mode == "masked" and (args.pp > 1 or args.tp > 1):
                # grammar-constrained decoding is single-chip only — on a
                # mesh config there is no masked ladder to prove
                print("🔎 prove masked-vs-unmasked: skipped (mesh config; "
                      "grammar is single-chip device-decode)")
                continue
            if mode == "masked":
                base = ga.engine_from_args(
                    _clone_args(args, grammar=False), d
                )
                var = ga.engine_from_args(_clone_args(args, grammar=True), d)
                try:
                    got = prove_masked_twin(base, var)
                finally:
                    base.close()
                    var.close()
            elif mode == "verify":
                e = ga.engine_from_args(
                    _clone_args(args, speculative="ngram"), d
                )
                try:
                    got = prove_verify_twin(e)
                finally:
                    e.close()
            elif mode == "paged":
                base = ga.engine_from_args(
                    _clone_args(args, kv_layout="contiguous"), d
                )
                var = ga.engine_from_args(
                    _clone_args(args, kv_layout="paged"), d
                )
                try:
                    got = prove_variant_pair(base, var, PAGED_VS_CONTIGUOUS)
                finally:
                    base.close()
                    var.close()
            else:  # int8: both engines paged, variant quantized
                base = ga.engine_from_args(
                    _clone_args(args, kv_layout="paged", kv_dtype=None), d
                )
                var = ga.engine_from_args(
                    _clone_args(args, kv_layout="paged", kv_dtype="int8"), d
                )
                try:
                    got = prove_variant_pair(base, var, INT8_VS_F32)
                finally:
                    base.close()
                    var.close()
            problems += got
            print(
                f"🔎 prove {DECLARED_SPECS[mode].name}: "
                + ("ok" if not got else f"{len(got)} problem(s)")
            )

    for prob in problems:
        print(f"  ! {prob}")
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
