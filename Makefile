# One-command gates (VERDICT r3 missing #6 — the round-3 snapshot
# shipped a red test because "suite green" wasn't a single command).
# Mirrors the reference's Makefile test target (reference Makefile:20-26).
#
#   make test      run the full suite (the end-of-round gate)
#   make lint      syntax-compile every source file, then simonlint —
#                  the first-party static analysis framework
#                  (tools/simonlint/, docs/STATIC_ANALYSIS.md): unused
#                  imports, mutable defaults, broad/silent except, I/O
#                  without timeouts, bare prints, JAX trace-safety +
#                  recompile hazards, lock discipline, and the dataflow
#                  rules (lock-order/blocking-under-lock, dtype/transfer
#                  drift, deadline discipline, error taxonomy).
#                  Incremental: unchanged files answer from
#                  .simonlint_cache/ (make lint NO_LINT_CACHE=1 or
#                  --no-cache for a cold run)
#   make check     lint + test
#   make examples  run both quickstart configs end to end
#   make bench     one bench line (SIMON_BENCH selects the scenario)

PY ?= python

.PHONY: test lint check examples bench

test:
	$(PY) -m pytest tests/ -q

lint:
	$(PY) -m compileall -q open_simulator_tpu tools tests bench.py __graft_entry__.py chip_smoke.py
	$(PY) -m tools.simonlint $(if $(NO_LINT_CACHE),--no-cache,)

check: lint test

examples:
	$(PY) -m open_simulator_tpu.cli apply -f example/simon-config.yaml --format json
	$(PY) -m open_simulator_tpu.cli apply -f example/simon-gpushare-config.yaml --format json

bench:
	$(PY) bench.py

# heavier after-kernel-change sweep on real TPU (compiled kernel vs XLA
# scan across randomized mixed-feature scenarios incl. storage and the
# streamed term layout)
deep-conformance:
	$(PY) tools/deep_conformance.py
