#!/usr/bin/env bash
# Fast CI signal, in dependency-free-first order:
#
#   1. repro-lint (tools/reprolint, docs/analysis.md): the AST
#      invariant gate — tracer hygiene, PRNG rotation, bit-exact
#      reductions, registry contracts, pallas kernel contracts,
#      donation safety.  Pure stdlib, sub-second; findings must exactly
#      match tools/reprolint/baseline.json.  The JSON artifact lands in
#      experiments/reprolint.json (git-ignored).
#   2. pyright (scripts/typecheck.sh) over src/repro/core — skipped
#      with a notice when pyright is not installed.
#   3. the fast tier-1 subset (strategy-registry equivalence, sparsity
#      + Top-K selector layer incl. the interpret-mode pallas
#      parity/contract tests from tests/test_selectors.py and the
#      exact_topk deprecation check, communication ledger, engine
#      registry/callback/chunking units from tests/test_engine.py and
#      tests/test_async_*.py (incl. the sparse-aggregation sim==async
#      bit-equality anchor), the fused one-pass transport
#      differential/property layer from tests/test_fused_transport.py
#      and tests/test_sparse_aggregation.py, the sharded-params 2-D mesh
#      differential subset from tests/test_sharded_multidevice.py
#      (bit-equality + sharding inspection read from the file's one
#      forced 8-device child, which runs the whole strategy matrix and
#      the resume leg: about 90 s more than one strategy alone), the
#      reprolint rule fixtures) — everything tagged @pytest.mark.fast.
#   4. the docs gate (scripts/check_docs.py: README/docs code
#      references and registry tables must resolve,
#      examples/quickstart.py must run).
#   5. a multi-tenant serving smoke: the continuous-batching engine over
#      a tiny arch, 4 adapters, 8 requests (repro.launch.serve).
#   6. the population-scaling smoke (docs/scale.md): the 1e4-client
#      host-store run rides the fast tier as
#      tests/test_population.py::test_population_smoke_1e4_clients;
#      benchmarks/population_bench.py then runs in BENCH_QUICK mode
#      (1e3/1e4 sweep, prefetch on/off) and regenerates
#      BENCH_population.json, asserting the one-bulk-H2D-per-round
#      transfer contract along the way.
#
# The full tier-1 suite (ROADMAP.md) still covers the slow
# model-training paths.
#
#   scripts/ci_fast.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."
mkdir -p experiments
python -m tools.reprolint src tests --json experiments/reprolint.json
scripts/typecheck.sh
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m pytest -q -m fast "$@"
python scripts/check_docs.py
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m repro.launch.serve \
    --arch yi-9b --clients 4 --pages 2 --lanes 2 --requests 8 --max-len 32
PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python benchmarks/population_bench.py --quick
