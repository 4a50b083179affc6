#!/usr/bin/env bash
# CI entry point: tier-1 tests + the three process-level smokes.
#
# Runs entirely on CPU — the Pallas kernels execute in interpret mode
# there (repro.kernels.backend: the platform decides), so this validates
# kernel semantics and the process plumbing without TPU hardware.  Speed
# is measured only on the chip, by the harness in benchmarks/chip/
# (BENCHMARK.json).
#
# Usage: tools/ci.sh  (from the repo root)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "=== tier-1 tests ==="
python -m pytest -x -q

echo "=== loopback e2e smoke: 1 shard server + 2 client processes ==="
# Real processes over 127.0.0.1 speaking the framed protocol end to end;
# the smoke asserts both client processes and an in-process reference
# agree on the final shared-statistics checksums (BSP bit-exactness
# across the socket).  timeout(1) guards against a hung server — a
# protocol bug must fail CI, not wedge it.
timeout 540 python -m repro.launch.loopback --smoke

echo "=== tcp kill-and-rejoin smoke: chaos proxy + shard restart + worker rejoin ==="
# The DESIGN.md §13 acceptance run as a process-level smoke: a BSP
# loopback run through chaos proxies (connection drop on the push path)
# in which one shard-server process is killed at its round barrier and
# restarted from its snapshot (--restore --ports, same addresses) and
# one worker process is killed mid-run and relaunched with --restore.
# The smoke asserts exactly one restart of each, that the scheduled
# drop fired, and that the final checksums are bit-exact with the
# undisturbed in-process run.  timeout(1) again guards against hangs.
timeout 540 python -m repro.launch.loopback --failover-smoke

echo "=== serve e2e smoke: 1 inference server + 2 concurrent client processes ==="
# The DESIGN.md §14 acceptance as a process-level smoke: train a small
# model, snapshot it, boot an inference-server process from the
# checkpoint and two concurrent client processes over 127.0.0.1, and
# require every served result checksum to be bit-identical to an
# in-process FoldInEngine replay of the same requests (the determinism
# contract across process + socket boundaries).  timeout(1) again
# guards against a hung batcher.
timeout 540 python -m repro.launch.serve --smoke
