#!/usr/bin/env bash
# Local/CI gate — the same three stages .github/workflows/ci.yml runs,
# for environments without Actions (and for preflight before pushing):
#
#   1. repo lint            (scripts/dlt_lint.py — AST rules, dlt pragmas)
#   2. graph audit          (tiny config, full warm-key ladder incl. the
#                            prefix-cache copy/extract programs: dtypes,
#                            collective budgets, KV donation, shardings)
#   2b. graph contracts      (scripts/dlt_graph_diff.py: golden jaxpr
#                            fingerprints for every warm-ladder program
#                            across 4 configs — any structural drift fails
#                            with a ±primitive diff; 100% contract+golden
#                            coverage of warm_plan(); the differential
#                            equivalence prover for the paged/int8/verify
#                            variant axes)
#   3. analysis test suite  (pytest -m analysis: one suite per audit pass)
#   4. prefix-cache suite   (radix trie, token identity, eviction/pinning,
#                            sanitizer acceptance — fast subset member)
#   5. speculative suite    (draft sources, greedy verify identity at
#                            engine/batch/session/HTTP levels, verify
#                            buckets on the warm ladder)
#   6. tracing suite        (trace ring/sampling, span trees, Prometheus
#                            exposition format, /debug/trace + /metrics on
#                            a live server, flight recorder, zero-host-sync
#                            contract with tracing on)
#   7. profiling suite      (warm-ladder cost table analytic sanity +
#                            coverage, HBM ledger + drift detector,
#                            roofline/MFU/SLO gauge math, /debug/costs +
#                            /debug/profile on a live server, fatal-
#                            sanitizer cleanliness of every profiling path)
#   8. paged-kv suite       (page pool alloc/COW/refcounts, paged-vs-
#                            contiguous token identity at engine/session/
#                            HTTP levels, zero-copy prefix sharing,
#                            exhaustion park/shed, sanitizer acceptance,
#                            the fatal-sanitizer /v1/chat regression)
#   8b. kv-quant suite       (int8 KV: quantization laws, f32 wire through
#                            gather/scatter, fused page-table-aware decode
#                            kernel numerics + the gather-free jaxpr pin,
#                            stored-width census/ledger honesty, equal-
#                            budget capacity, int8 ladder audit, sanitizer
#                            acceptance, --kv-dtype over HTTP)
#   8c. grammar suite        (structured decoding: regex/schema -> token
#                            DFA compile + bomb defenses, arena spans +
#                            session semantics, masked engine/speculative/
#                            BatchSession streams with zero illegal tokens,
#                            response_format over HTTP incl. SSE + 400s,
#                            fatal-sanitizer mixed co-tenancy)
#   9. fleet suite          (gateway federation scraper under the chaos
#                            harness, per-replica signal table + staleness,
#                            federated /metrics format, goodput-ledger
#                            token identity, batch timeline, /debug/config)
#  10. router suite         (cache-aware routing: scoring purity, rendez-
#                            vous affinity stability, the 4-replica >=2x
#                            concentration twin; disaggregated serving:
#                            KV wire codec, token identity vs unified,
#                            chaos mid-transfer degradation)
#  10b. kv-movement suite    (runtime/kv_transport.py: content-addressed
#                            page naming, transport selection + device
#                            registry, mesh-paged twins pp>1/tp>1 with
#                            collective-budget parity + zero-recompile
#                            sanitizer run, device-path disagg identity,
#                            page-skip re-sends, device chaos degradation)
#  11. scheduler suite      (SLO-class scheduling: priority queues,
#                            quotas, preemption observable end to end on
#                            a live engine; autoscaler tick policy; the
#                            10-replica load-twin smoke + the mixed-class
#                            SLO and drain-handoff acceptance twins)
#  11b. robustness suite     (supervised engine lifecycle: rebuild-in-
#                            place token identity, recovering/failed
#                            health states, restart budget; poison-
#                            request quarantine at gateway + replica;
#                            end-to-end deadlines; the poison+replica-
#                            kill fleet chaos twin — plus a cross-suite
#                            single-process slow pair proving a torn-down
#                            server's sealed sentinel cannot condemn a
#                            later suite's engine builds)
#  11c. gateway-ha suite     (gateway failure domain: warm-restart
#                            recovery of locality/quarantine/drain state
#                            from the fleet, active-active peering with
#                            LWW deltas + leader election, the strike
#                            discount, GatewayServer thread lifecycle,
#                            and the twin failover/restart chaos proofs)
#  11d. kv-integrity suite   (data-plane integrity: checksummed KV wire
#                            codec + receipt verification, seeded codec
#                            fuzz, corruption chaos trio + device corrupt
#                            modes degrading token-identical, corrupt-
#                            peer quarantine, wire-version skip-peer)
#
# Pass --full to also run the tier-1 fast subset (-m 'not slow').
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

echo "== dlt-lint =="
python scripts/dlt_lint.py

echo "== graph audit (tiny config, --costs coverage) =="
python -m distributed_llama_tpu.analysis.graph_audit --costs

echo "== graph audit (paged KV ladder, --costs coverage) =="
python -m distributed_llama_tpu.analysis.graph_audit --kv-layout paged --costs

echo "== graph audit (int8 paged ladder, page-table decode kernel) =="
# interpret mode makes the page-table kernel trace-eligible on
# CPU so the audited ladder IS the int8 serving shape (zero pool gathers)
DLT_PALLAS_INTERPRET=1 \
  python -m distributed_llama_tpu.analysis.graph_audit \
  --kv-layout paged --kv-dtype int8 --costs

echo "== graph audit (MESH-paged ladder, pp=2 x tp=2) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m distributed_llama_tpu.analysis.graph_audit \
  --kv-layout paged --pp 2 --tp 2 --speculative off

echo "== graph contracts (golden fingerprints + coverage, 4 configs) =="
# every warm_plan() program re-traced and diffed against the blessed
# goldens in analysis/golden/ — ANY structural drift fails with a
# ±primitive diff; --coverage proves contract + golden per ladder entry.
# Intentional graph changes: scripts/dlt_graph_diff.py --bless (per
# config) and put the golden diff in the PR.
python scripts/dlt_graph_diff.py --check --coverage
python scripts/dlt_graph_diff.py --check --coverage --kv-layout paged
DLT_PALLAS_INTERPRET=1 \
  python scripts/dlt_graph_diff.py --check --coverage \
  --kv-layout paged --kv-dtype int8
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python scripts/dlt_graph_diff.py --check --coverage \
  --kv-layout paged --pp 2 --tp 2 --speculative off
# the tiny Olmo-Hybrid's ladder (a period of layers, the recurrent arm, the
# gated-delta decode kernel's body under interpret mode)
DLT_PALLAS_INTERPRET=1 \
  python scripts/dlt_graph_diff.py --check --coverage --arch olmo_hybrid \
  --kv-layout paged --speculative off --prefix-cache-mb 0
# the tiny Granite-Hybrid's ladder (runs of state-space layers in inner scans,
# the state-space decode kernel's body, head 64 stored as 128), in bfloat16
DLT_PALLAS_INTERPRET=1 \
  python scripts/dlt_graph_diff.py --check --coverage --arch granite_hybrid \
  --kv-layout paged --speculative off --prefix-cache-mb 0 --compute-dtype bfloat16

echo "== graph contracts (MASKED ladder goldens, grammar arena) =="
# the grammar-capable engine's decode/verify programs carry the mask-table
# operand pair — their own golden configs (config_key _gr suffix)
python scripts/dlt_graph_diff.py --check --coverage --grammar
python scripts/dlt_graph_diff.py --check --coverage --grammar --kv-layout paged

echo "== graph contracts (differential equivalence prover) =="
# paged = contiguous + page tables; int8 = f32 + quantization (zero pool
# gathers); verify_k = prefill twin + argmax; masked = unmasked +
# gather/where (dots + collectives pinned) — anything else fails by name.
# The first three are statements about the HLO formulation (no Pallas on
# the CPU: the gather arm); int8-vs-f32 is proved in interpret mode, where
# both sides' decode programs take the page-table kernel
python scripts/dlt_graph_diff.py --prove paged
python scripts/dlt_graph_diff.py --prove verify
python scripts/dlt_graph_diff.py --prove masked
DLT_PALLAS_INTERPRET=1 python scripts/dlt_graph_diff.py --prove int8

echo "== analysis suite (pytest -m analysis) =="
python -m pytest tests/ -q -m analysis -p no:cacheprovider

echo "== prefix-cache suite =="
python -m pytest tests/test_prefix_cache.py -q -p no:cacheprovider

echo "== speculative suite =="
python -m pytest tests/test_speculative.py -q -p no:cacheprovider

echo "== tracing suite =="
python -m pytest tests/test_tracing.py -q -p no:cacheprovider

echo "== profiling suite =="
python -m pytest tests/test_profiling.py -q -p no:cacheprovider

echo "== paged-kv suite =="
python -m pytest tests/test_paged_kv.py -q -p no:cacheprovider

echo "== kv-quant suite (int8 KV + fused paged decode attention) =="
python -m pytest tests/test_kv_quant.py -q -p no:cacheprovider

echo "== grammar suite (structured decoding: DFA, arena, masked engine, HTTP) =="
python -m pytest tests/test_grammar.py -q -p no:cacheprovider

echo "== fleet suite (federation + goodput + timeline) =="
python -m pytest tests/test_fleet.py tests/test_goodput.py -q -p no:cacheprovider

echo "== router suite (cache-aware routing + disaggregated serving) =="
python -m pytest tests/test_router.py tests/test_disagg.py -q -p no:cacheprovider

echo "== kv-movement suite (transports, mesh-paged twins, page shipping) =="
python -m pytest tests/test_kv_transport.py -q -p no:cacheprovider

echo "== scheduler suite (SLO classes + autoscaler + load twin) =="
python -m pytest tests/test_scheduler.py tests/test_loadtwin.py -q -p no:cacheprovider

echo "== robustness suite (supervisor + quarantine + deadlines + chaos twin) =="
python -m pytest tests/test_supervisor.py tests/test_quarantine.py \
  tests/test_deadline.py -q -p no:cacheprovider

echo "== gateway-ha suite (recovery + peering + failover chaos) =="
python -m pytest tests/test_gateway_ha.py -q -p no:cacheprovider

echo "== kv-integrity suite (checksummed transfers + corrupt-peer quarantine) =="
python -m pytest tests/test_kv_integrity.py -q -p no:cacheprovider

echo "== cross-suite sentinel-lifecycle pair (single process, slow-marked) =="
# two suites whose servers warm + seal fatal-capable sentinels in ONE
# process: green only while server teardown releases the sentinel
# (the PR 13 combined-slow-run pollution class; see ApiState.close)
python -m pytest tests/test_supervisor.py tests/test_speculative.py \
  -q -m slow -p no:cacheprovider

if [[ "${1:-}" == "--full" ]]; then
  echo "== tier-1 fast subset =="
  python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider
  echo "== heavyweight (slow-marked) suite =="
  python -m pytest tests/ -q -m slow --continue-on-collection-errors -p no:cacheprovider
fi

echo "ci_check: all stages passed"
