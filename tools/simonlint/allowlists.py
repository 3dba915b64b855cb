"""Audited allowlists — the escape hatch that leaves a paper trail.

Every entry is keyed by (repo-relative path, enclosing function) so
line drift cannot rot it, and carries a one-line justification in the
comment above it. The test suite asserts every listed file still
exists (tests/test_simonlint.py). Unlike pragmas, allowlist entries are
not usage-checked — they cover whole functions, not lines — so prefer
a `# simonlint: disable=RULE` pragma (which IS usage-checked via
SL001) for single-line exemptions.
"""

from __future__ import annotations

from typing import Set, Tuple

Key = Tuple[str, str]

# --------------------------------------------------------------- BLE001/S110
# Broad handlers audited as legitimate last-resort degradations: each
# logs a warning and/or records a trace note, then falls back to a
# correct (slower) path — never a silent swallow. Anything new must
# catch specific exception types or earn an entry here with the same
# audit.
BROAD_EXCEPT_ALLOW: Set[Key] = {
    ("open_simulator_tpu/apply/applier.py", "_plan_with_probes"),
    ("open_simulator_tpu/apply/applier.py", "_sweep_min_count"),
    ("open_simulator_tpu/apply/interactive.py", "_make_evaluator"),
    # narrow-typed parse cascade (int -> float -> MISSING is the
    # template grammar, not a swallowed error) and best-effort tempfile
    # cleanup on close — audited silent-pass survivors
    ("open_simulator_tpu/models/chart.py", "_eval_atom"),
    ("open_simulator_tpu/models/kubeclient.py", "close"),
    # ladder executor: classifies via classify_device_error and either
    # re-raises typed or downgrades with a trace note — never swallows
    ("open_simulator_tpu/runtime/guard.py", "run_laddered"),
    # signal-handler restore at interpreter teardown: ValueError means
    # "not the main thread anymore", there is nothing left to restore
    ("open_simulator_tpu/runtime/budget.py", "sigint_to_budget"),
}

# ------------------------------------------------------------------- S113
# Audited call sites allowed without an explicit timeout: every other
# first-party I/O call names its timeout (runtime/retry.py holds the
# configurable defaults).
IO_TIMEOUT_ALLOW: Set[Key] = {
    # Popen has no timeout= (it does not wait); the spawn readiness
    # wait that follows is bounded by ReplicaProcess.ready_timeout_s
    ("open_simulator_tpu/fleet/replica.py", "_spawn_once"),
}

# ------------------------------------------------------------------- T201
# Files whose job IS terminal output — the CLI command surface.
# Everything else in open_simulator_tpu/ must route output through the
# report writer / logging / obs spans, or name its stream with file=.
PRINT_ALLOW_FILES: Set[str] = {
    "open_simulator_tpu/cli.py",
}
# Audited individual print sites. Currently empty: the non-CLI
# survivors all pass an explicit file= (interactive.py's shell writes
# to its injected fout).
PRINT_ALLOW: Set[Key] = set()

# ------------------------------------------------------------------ JAX002
# jit wrappers created inside a function body but provably compiled
# once: the creation is behind a cache-miss guard and the wrapper is
# stored somewhere the checker's assignment analysis cannot follow.
JAX002_ALLOW: Set[Key] = {
    # `@jax.jit def call(...)` is built once per _COMPILED_CACHE key
    # (the early return on a hit above it) and stored via
    # _Compiled(fn=call) — a dataclass hop the local-escape analysis
    # cannot see through
    ("open_simulator_tpu/ops/pallas_scan.py", "kernel_call"),
}

# ------------------------------------------------------------------ JAX001
# Traced-reachable host calls audited as trace-safe. Currently empty:
# the guarded host path in ops/scan.features_of carries a def-line
# pragma instead (it is one function, and the pragma is usage-checked).
JAX001_ALLOW: Set[Key] = set()

# ----------------------------------------------------------------- CONC001
# Unlocked accesses to lock-guarded fields audited as safe. Currently
# empty: the documented benign races (memo fast path, hot-path enabled
# reads, caller-holds-lock helpers) carry usage-checked pragmas at the
# site instead.
CONC001_ALLOW: Set[Key] = set()

# ----------------------------------------------------------------- CONC002
# Functions exempt from the lock-order / blocking-under-lock dataflow.
# Currently empty: the one audited in-tree case (JsonlSink._emit keeps
# its per-line fsync under the sink's own single-purpose I/O lock)
# carries a usage-checked def-line pragma with the justification at
# the code instead.
CONC002_ALLOW: Set[Key] = set()

# ------------------------------------------------------------------- RT001
# Budget-scoped while loops audited as exempt from the
# check-on-every-path discipline. Prefer a usage-checked RT001 pragma
# at the loop over an entry here.
RT001_ALLOW: Set[Key] = set()

# ------------------------------------------------------------------ JAX003
# Engine-directory functions exempt from the dtype/transfer dataflow.
# Prefer a usage-checked JAX003 pragma at the site over an entry here
# (sweep.find_min_count_multi's one counted sync per shape bucket
# carries one).
JAX003_ALLOW: Set[Key] = set()

# ------------------------------------------------------------------ EXC001
# Whole modules whose JOB is parsing/validation: stdlib
# ValueError/TypeError raises there ARE the input-error surface
# (InputError is itself a ValueError; these modules sit below it and
# their internal `except ValueError` cascades must keep catching their
# own raises). Anything outside these files needs a per-function entry
# below or a typed taxonomy error.
EXC001_VALIDATION_FILES: Set[str] = {
    # the Go-compatible quantity grammar: parse errors are ValueErrors
    # by contract (validation.py wraps them into field-scoped errors)
    "open_simulator_tpu/utils/quantity.py",
    # Go math/rand reimplementation: argument-contract checks mirror
    # the stdlib's panics; callers treat them as programming errors
    "open_simulator_tpu/utils/gorand.py",
    # KubeSchedulerConfiguration parser: every raise is a config-file
    # diagnosis, wrapped by load_scheduler_config into one message
    "open_simulator_tpu/scheduler/schedconfig.py",
    # snapshot document validation (version/shape checks on load)
    "open_simulator_tpu/scheduler/snapshot.py",
    # --inject spec grammar: modifier parsing raises ValueError and
    # parse_spec's own `except ValueError` cascade wraps every one
    # into a clause-scoped InputError (the quantity.py pattern)
    "open_simulator_tpu/runtime/inject.py",
}

# Individual validation-boundary functions allowed to raise stdlib
# ValueError/TypeError: constructor argument checks and request/record
# parsers whose callers catch ValueError by contract.
EXC001_ALLOW: Set[Key] = {
    # HTTP request parsing: the handler catches ValueError -> 400
    ("open_simulator_tpu/serve/server.py", "parse_request_body"),
    ("open_simulator_tpu/serve/server.py", "_decode_app_yaml"),
    # constructor argument validation (the Python idiom; callers that
    # pass literals deserve the loud TypeError/ValueError)
    ("open_simulator_tpu/serve/coalescer.py", "__init__"),
    ("open_simulator_tpu/serve/sessions.py", "__init__"),
    ("open_simulator_tpu/runtime/budget.py", "__init__"),
    ("open_simulator_tpu/runtime/guard.py", "run_laddered"),
    ("open_simulator_tpu/resilience/chaos.py", "__init__"),
    ("open_simulator_tpu/scheduler/oracle.py", "__init__"),
    ("open_simulator_tpu/scheduler/plugins.py", "register"),
    ("open_simulator_tpu/testing.py", "_check_positionals"),
    # journal/decision-log record parsing: the raise IS the control
    # flow (caught as ValueError in the same function to classify a
    # torn tail vs interior damage)
    ("open_simulator_tpu/runtime/journal.py", "resume"),
    ("open_simulator_tpu/runtime/journal.py", "rewrite"),
    ("open_simulator_tpu/runtime/checkpoint.py", "load_checkpoint"),
    ("open_simulator_tpu/shadow/log.py", "read_decision_log"),
    ("open_simulator_tpu/shadow/log.py", "from_record"),
    # API-contract preconditions on the scan entry points (caller bug,
    # not recoverable input; ValueError mirrors numpy's own contract
    # errors these sit beside)
    ("open_simulator_tpu/ops/scan.py", "run_scan_masked"),
    ("open_simulator_tpu/ops/pallas_scan.py", "run_scan_pallas"),
    ("open_simulator_tpu/scheduler/engine.py", "scan_scenarios"),
    ("open_simulator_tpu/scheduler/oracle.py", "evict"),
    ("open_simulator_tpu/scheduler/oracle.py", "remove_pod_from_node"),
    # extenders config section validation (wrapped upstream into the
    # config-load diagnosis)
    ("open_simulator_tpu/scheduler/extender.py", "extenders_from_config_doc"),
    # CLI flag-literal parsing (argparse surfaces it as a usage error)
    ("open_simulator_tpu/cli.py", "_parse_taint"),
}
