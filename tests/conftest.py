"""Test harness configuration.

Forces JAX onto the CPU backend with 8 virtual devices BEFORE jax is
imported, so every test exercises real multi-device semantics
(pjit/shard_map over a Mesh) without TPU hardware.  The reference had
no equivalent (its cluster paths were only testable by running the
cluster, SURVEY.md section 4); this is the TPU-native answer.  The
variables also flow to every subprocess the tests spawn.
"""

import atexit
import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# Synchronous host feed on the 8-device mesh: the prefetch worker's
# device_put races the consumer's dispatch inside the CPU PJRT client
# and intermittently aborts the process (see data/pipeline.py:prefetch).
# Tests that exercise the threaded worker itself (single device — the
# case the chip has) override this locally.
os.environ.setdefault("FAA_PREFETCH_SYNC", "1")
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The persistent compile cache is always on (core/compilecache.py);
# place it in a session directory so the suite — and every subprocess it
# spawns — shares one cache without writing into the checkout.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_dir = tempfile.mkdtemp(prefix="faa-test-jax-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
    atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

import pytest  # noqa: E402


#: metrics whose children a benchmark reader sums over the whole registry
_READ_WHOLE = ("faa_moe_", "faa_attention_key_tiles_total")


@pytest.fixture(scope="module", autouse=True)
def expert_layer_metrics_end_with_their_module():
    """A token program publishes a counter and a gauge a layer
    (``faa_moe_*{layer=...}``), and the benchmark's token programs hand the
    readers every such child the process's registry holds.  What a test
    module's runs registered is taken out again when the module ends, so
    that another token file's tests in the same xdist worker read their
    own model's layers alone (PR 41 saw ``layer="mtp"`` survive into the
    Kimi file's rehearsal).  Counters that were there keep counting.  The
    same for the key tiles the attention cores' loops meet
    (``faa_attention_key_tiles_total{span,kind}``), whose reader divides the
    registry's sums over every span: a file that traced fused cores under a
    span left its tiles to whichever rehearsal shared its worker (PR 50's
    ``tests/test_attention_groups.py`` moved the files about, and
    ``swa_key_tiles_visited_share`` read 93% of a program that skips
    nothing)."""
    from fast_autoaugment_tpu.core import telemetry

    registry = telemetry.registry()

    def children():
        with registry._lock:
            return {key for key in registry._metrics if key[0].startswith(_READ_WHOLE)}

    held = children()
    yield
    new = children() - held
    with registry._lock:
        for key in new:
            registry._metrics.pop(key, None)


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def spawn_logged(tmp_path):
    """Popen factory for multi-process drills: each child writes stdout
    and stderr to a FILE, and ``finish(proc, timeout)`` waits for it and
    returns what it wrote.  Children on PIPEs read one after the other
    deadlock as soon as one fills its 64 KiB pipe while the test blocks
    on another's — and a JAX process is chatty (XLA:CPU logs ~2 KB per
    executable it loads from the persistent cache)."""
    import subprocess

    def spawn(cmd, *, env, name, **popen_kw):
        path = tmp_path / f"{name}.log"
        with open(path, "w") as log:
            proc = subprocess.Popen(cmd, env=env, stdout=log,
                                    stderr=subprocess.STDOUT, **popen_kw)
        proc.log_path = path
        return proc

    def finish(proc, timeout):
        proc.wait(timeout=timeout)
        return proc.log_path.read_text()

    spawn.finish = finish
    return spawn


@pytest.fixture()
def rng():
    import jax

    return jax.random.PRNGKey(0)
