#!/bin/sh
# Run-script analog of the reference's test.sh (test.sh:8): positional
# hyperparameters -> the training CLI on a dataset directory.
#   usage: sh scripts/test.sh <lr> <wd> <decay-rate> <dropout> <layers> <epochs> [extra args...]
# The reference's Legion resource flags (-ll:gpu/-ll:cpu/-ll:fsize/
# -ll:zsize) have no TPU analog: XLA owns HBM, --parts picks the mesh.
set -e
LR=$1; WD=$2; DR=$3; DROP=$4; LAYERS=$5; EPOCHS=$6
shift 6 || true
# concurrency/signal-safety preflight (roc-lint level six): pure-AST
# and jax-free, so it fails in milliseconds on a lock-order cycle, a
# predicate-less Condition.wait, or an unsafe signal handler before
# the (slower) trace stage below even starts; the --json report
# carries the discovered thread/lock/handler surface for
# `python -m roc_tpu.report --concurrency <file>`
CONC_REPORT="${TMPDIR:-/tmp}/roc_concurrency_report.json"
python -m roc_tpu.analysis --select concurrency --json \
    > "$CONC_REPORT" || { cat "$CONC_REPORT"; exit 1; }
# sharding & replication preflight (roc-lint level seven): walk both
# trainers' candidate jaxprs on the CPU rig (no compiles) and hold
# the replication ledger against the ratcheted replication_budget —
# a PR that adds a replicated buffer, voids a donation under
# sharding, or re-gathers a constrained tensor to full width fails
# HERE, before chip time; the --json report carries the ledger +
# mesh-portability worklist for
# `python -m roc_tpu.report --sharding <file>`
SHARD_REPORT="${TMPDIR:-/tmp}/roc_sharding_report.json"
python -m roc_tpu.analysis --select sharding --json \
    > "$SHARD_REPORT" || { cat "$SHARD_REPORT"; exit 1; }
# protocol audit + bounded model check preflight (roc-lint level
# eight): pure-AST wire-vocabulary extraction over the serve/ckpt
# state machines plus an exhaustive bounded BFS over crash/interleave
# schedules of the router lifecycle, the v3 two-phase commit, and the
# versioned-table swap — jax-free, millisecond class; a sent-but-
# unhandled wire kind, a dropped field contract, or an invariant
# violation fails HERE.  The --json report carries the surface for
# `python -m roc_tpu.report --protocol <file>`
PROTO_REPORT="${TMPDIR:-/tmp}/roc_protocol_report.json"
python -m roc_tpu.analysis --select protocol --json \
    > "$PROTO_REPORT" || { cat "$PROTO_REPORT"; exit 1; }
# pre-flight static analysis (roc-lint): regressions against the
# perf invariants fail HERE, before any chip time is spent.  The run
# also prints the program-space compile-budget delta vs
# scripts/lint_baseline.json (shrink-only ratchet, red on a tty when
# it grew) — a PR that adds a compiled-program shape shows it before
# the test tier starts.
python -m roc_tpu.analysis --strict
# serving SLO smoke preflight (PR 17): export a predictor artifact,
# cold-load it in subprocess replicas, drive a 100-query load gen
# with the declared availability/latency objectives armed, and
# require Router.health() green — a serving tier whose SLO engine
# reports a breach on quiet CPU traffic must not reach chip time
# (set -e makes the nonzero exit fatal)
python benchmarks/micro_serve.py --slo-smoke --cpu \
    --queries 100 --nodes 2000 > /dev/null
# quantized-serving drift-gate preflight (PR 19): export int8 (the
# measured drift gate must pass — export refuses past threshold),
# cold-load, 100-query load gen, served answers bit-equal to the
# gated values — a drifting quantization must not reach chip time
# (set -e makes the nonzero exit fatal)
python benchmarks/micro_serve.py --quant-smoke --cpu \
    --queries 100 --nodes 2000 > /dev/null
# sharded-serving smoke preflight (PR 20): export --shards 2, cold-
# load one slice (zero new compiles — slice shapes ride the same
# bucket quantization), then a 2-replica sharded Router under a byte
# cap below the full table serves a 100-query load gen whose batches
# straddle the shard boundary, bit-exact via the cross-shard gather
# leg — a fleet that cannot gather across its own shards must not
# reach chip time (set -e makes the nonzero exit fatal)
python benchmarks/micro_serve.py --shard-smoke --cpu \
    --queries 100 --nodes 2000 > /dev/null
exec python -m roc_tpu.train.cli \
    -lr "$LR" -decay "$WD" -decay-rate "$DR" -dropout "$DROP" \
    -layers "$LAYERS" -e "$EPOCHS" -file dataset/reddit-dgl "$@"
