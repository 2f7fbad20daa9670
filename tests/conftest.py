"""Test config: force CPU with 8 virtual devices so multi-chip sharding
paths (mesh simulator, xla_ici backend, FSDP/TP shardings) are exercised
without TPU hardware — per the driver's dryrun contract."""
import os

# XLA_FLAGS is read when the CPU client is first created, so setting it
# here (before any backend init) is effective.
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent XLA compile cache: CPU-gate wall clock is dominated by XLA
# compiles, and the cache cuts a warm `pytest -m "not slow"` by minutes.
# Same rule as the program (fedml_tpu/utils/compile_cache.py): the env's
# directory if set, else the fixed <checkout>/.jax_cache. Exported via
# env (not only the config API) so subprocess tests (cross-device
# clients, node agents, spawned job ranks) inherit it.
from fedml_tpu.utils.compile_cache import compile_cache_dir  # noqa: E402

_cache_dir = os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", compile_cache_dir())

# Agents probe accelerator inventory in a subprocess (a fresh jax import);
# pin the answer so tests never pay that — inherited by spawned agents too.
os.environ.setdefault(
    "FEDML_TPU_RESOURCES",
    '{"platform": "cpu", "device_count": 8, "device_kind": "cpu"}',
)

import jax  # noqa: E402

# through the config API too: it holds even if jax was imported (and read
# JAX_PLATFORMS) before this file ran, as long as no backend exists yet
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", _cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
# Tests measure correctness, not runtime speed: skip the expensive XLA
# optimization passes (~25% less compile wall-clock on a cold cache).
# FEDML_TPU_FULL_OPT=1 (nightly CI) keeps default optimizations so the
# configuration production runs is compiled at least once a day —
# numerics demonstrably shift with opt level.
if os.environ.get("FEDML_TPU_FULL_OPT") != "1":
    jax.config.update("jax_disable_most_optimizations", True)
    os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")  # subprocesses
else:
    os.environ.pop("JAX_DISABLE_MOST_OPTIMIZATIONS", None)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_singletons():
    """Trust-stack singletons are process-global; isolate tests."""
    yield
    from fedml_tpu.core.alg_frame.params import Context
    from fedml_tpu.core.dp.fedml_differential_privacy import FedMLDifferentialPrivacy
    from fedml_tpu.core.fhe.fhe_agg import FedMLFHE
    from fedml_tpu.core.security.attacker import FedMLAttacker
    from fedml_tpu.core.security.defender import FedMLDefender

    FedMLAttacker.reset()
    FedMLDefender.reset()
    FedMLDifferentialPrivacy.reset()
    FedMLFHE.reset()
    Context.reset()
    # telemetry globals: fresh registry + tracer + flight recorder +
    # health-log handle per test so counters, span sinks and crash rings
    # never leak across tests
    from fedml_tpu import telemetry
    from fedml_tpu.telemetry.health import reset_health_log

    telemetry.reset_live_plane()
    telemetry.reset_registry()
    telemetry.reset_tracer()
    telemetry.reset_flight_recorder()
    # profiling globals: fresh program-catalog accounting (compiled
    # variants survive — recompiling per test would be the regression)
    # and a fresh trace controller so captures never leak across tests
    telemetry.reset_catalog()
    telemetry.reset_trace_controller()
    reset_health_log()
    # serving-event burst-dedupe state is module-global too
    from fedml_tpu.serving.events import reset_serving_events

    reset_serving_events()
