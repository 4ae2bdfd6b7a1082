"""Pytest bootstrap: make the hypothesis fallback shim available before any
test module runs its ``from hypothesis import ...`` line (helpers.py holds
the shim so it is importable outside pytest too), and register the fixed
CI profile so property runs are reproducible per PR."""

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

# Multi-device CPU for the sharded-plane tests: REPRO_CPU_DEVICES=n
# forces n host devices before jax's backend initializes, so shard_map
# really runs multi-device (the CI fleet lane sets 8).  Opt-in only —
# the main suite keeps whatever device count the backend picks up, and
# sharded tests skip gracefully on a single device.  A pre-existing
# XLA_FLAGS device-count setting is always respected.
_n_cpu = os.environ.get("REPRO_CPU_DEVICES", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if (_n_cpu.isdigit() and int(_n_cpu) > 0
        and "xla_force_host_platform_device_count" not in _flags):
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={_n_cpu}").strip()

from helpers import install_hypothesis_shim  # noqa: E402

install_hypothesis_shim()

# Fixed-seed CI profile: with the real hypothesis package installed the
# "ci" profile derandomizes (stable examples per PR, no flaky shrink
# budget); the shim is already deterministic and ignores profiles, but
# exposes no-op register/load hooks so this block is package-agnostic.
from hypothesis import settings as _settings  # noqa: E402

if hasattr(_settings, "register_profile"):
    _settings.register_profile("ci", max_examples=24, deadline=None,
                               derandomize=True)
    profile = os.environ.get("HYPOTHESIS_PROFILE")
    if profile:
        _settings.load_profile(profile)
