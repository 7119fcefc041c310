"""What ``chip_smoke.py`` rests on, checked without a chip: it refuses to run
(and prints no result) off the TPU, its CPU rehearsal passes without ever
printing the TPU result, importing the program opens no backend, the compile
cache is placed from outside, a single host is not taken for a pod, and the
process layouts that cannot work on a TPU host are refused at once.
"""

import hashlib
import json
import os
import subprocess
import sys

import jax
import pytest

from distributed_pytorch_tpu import native
from distributed_pytorch_tpu.elastic import agent
from distributed_pytorch_tpu.parallel import bootstrap
from distributed_pytorch_tpu.serving import replica
from distributed_pytorch_tpu.utils import platform

REPO = platform.REPO_ROOT
SMOKE = os.path.join(REPO, "chip_smoke.py")
# The variables a one-chip v5e machine exports (chip_smoke.py prints them).
SINGLE_HOST = {
    "TPU_WORKER_ID": "0",
    "TPU_WORKER_HOSTNAMES": "localhost",
    "TPU_ACCELERATOR_TYPE": "v5litepod-4",
    "TPU_SKIP_MDS_QUERY": "true",
}


def run_smoke(*args, timeout=30, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "XLA_FLAGS",
                         platform.COMPILE_CACHE_ENV)}
    return subprocess.run(
        [sys.executable, SMOKE, *args], env={**base, **env},
        capture_output=True, text=True, timeout=timeout,
    )


# ------------------------------------------------------------------ refusals


@pytest.mark.parametrize(
    "args, env",
    [
        ((), {"JAX_PLATFORMS": "cpu"}),  # the driver's sandbox check
        (("--chips", "4"), {"JAX_PLATFORMS": "cpu"}),
        (("--rehearse",), {}),  # a rehearsal only ever runs on the CPU
        (("--rehearse",), {"JAX_PLATFORMS": "tpu"}),
    ],
)
def test_refuses_and_prints_no_result(args, env):
    done = run_smoke(*args, **env)
    assert done.returncode != 0
    assert '"ok"' not in done.stdout


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_cpu_rehearsal_passes_without_the_tpu_line(chips):
    """The whole script at a tiny size, kernels interpreted (~45 s: five
    interpreters each import the package). Run it before every chip call."""
    done = run_smoke(
        "--rehearse", "--chips", str(chips), timeout=600,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
    )
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] == "passed"
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips}
    assert '"ok": true' not in done.stdout


# ----------------------------------------------- a fresh process, looked at

MODULES = ("distributed_pytorch_tpu", "distributed_pytorch_tpu.serving",
           "distributed_pytorch_tpu.elastic", "chip_smoke")
PROBE = """
import importlib, json, sys
from jax._src import xla_bridge
out = {}
for name in %r:
    importlib.import_module(name)
    out[name] = xla_bridge.backends_are_initialized()
import jax
from distributed_pytorch_tpu.utils.platform import enable_compile_cache
out["cache"] = enable_compile_cache()
out["config"] = jax.config.jax_compilation_cache_dir
out["after_cache"] = xla_bridge.backends_are_initialized()
print(json.dumps(out))
""" % (MODULES,)


@pytest.fixture(scope="module")
def fresh_process():
    """One new interpreter with no cache variable set: what importing the
    program and placing the cache do before anything else has run."""
    env = {k: v for k, v in os.environ.items()
           if k != platform.COMPILE_CACHE_ENV}
    done = subprocess.run(
        [sys.executable, "-c", PROBE], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("module", MODULES)
def test_import_initialises_no_backend(fresh_process, module):
    """A parent that only imports the program can still hand the chip to a
    child."""
    assert fresh_process[module] is False


def test_cache_unset_is_one_fixed_path_in_the_checkout(
    fresh_process, monkeypatch
):
    """Equal across two processes (that one and this one), so the cache key
    never moves; set in the config and exported for children; no backend
    touched."""
    want = os.path.join(REPO, ".jax_cache")
    assert fresh_process["cache"] == fresh_process["config"] == want
    assert fresh_process["after_cache"] is False
    monkeypatch.delenv(platform.COMPILE_CACHE_ENV, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert platform.enable_compile_cache() == want
        assert os.environ[platform.COMPILE_CACHE_ENV] == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        os.environ.pop(platform.COMPILE_CACHE_ENV, None)


def test_cache_set_from_outside_sets_nothing_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv(platform.COMPILE_CACHE_ENV, str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert platform.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


# --------------------------------------------------- one host is not a pod


@pytest.mark.parametrize(
    "env, pod",
    [
        (SINGLE_HOST, False),
        ({"TPU_WORKER_ID": "0"}, False),
        ({"CLOUD_TPU_TASK_ID": "0"}, False),
        ({"TPU_WORKER_ID": "1", "TPU_WORKER_HOSTNAMES": "10.0.0.1,10.0.0.2"},
         True),
    ],
)
def test_single_host_never_waits_on_a_metadata_server(monkeypatch, env, pod):
    for var in ("TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES", "CLOUD_TPU_TASK_ID",
                "COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    for var, value in env.items():
        monkeypatch.setenv(var, value)
    calls = []
    monkeypatch.setattr(
        jax.distributed, "initialize", lambda *a, **k: calls.append((a, k))
    )
    monkeypatch.setattr(bootstrap, "_initialized", False)
    bootstrap.setup_distributed()
    assert bootstrap._on_tpu_pod() is pod
    assert calls == ([((), {})] if pod else [])


# ------------------------------------------- one process for each chip


@pytest.mark.parametrize(
    "nproc, chips, platforms, refused",
    [
        (2, 4, None, True),
        (2, 1, "tpu,cpu", True),
        (1, 4, None, False),  # one worker drives every local chip
        (2, 4, "cpu", False),  # CPU workers share nothing
        (2, 0, None, False),  # no TPU on this host
    ],
)
def test_agent_refuses_several_workers_on_a_tpu_host(
    monkeypatch, nproc, chips, platforms, refused
):
    monkeypatch.setattr(agent, "local_tpu_chips", lambda: chips)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    cfg = agent.ElasticConfig(
        nproc_per_node=nproc,
        env={} if platforms is None else {"JAX_PLATFORMS": platforms},
    )
    if refused:
        with pytest.raises(SystemExit, match="nproc-per-node 1"):
            agent._refuse_shared_tpu(cfg)
    else:
        agent._refuse_shared_tpu(cfg)


def test_replica_worker_refused_by_a_parent_that_holds_the_chip(monkeypatch):
    """At once, with the reason — not after the spawn timeout, with the
    worker's 'TPU is already in use'."""
    monkeypatch.setattr(replica, "holds_accelerator", lambda: True)
    spawned = []
    monkeypatch.setattr(
        replica.subprocess, "Popen", lambda *a, **k: spawned.append(a)
    )
    with pytest.raises(replica.ReplicaError, match="holds the accelerator"):
        replica.ProcessReplicaClient({}, env={"JAX_PLATFORMS": "tpu,cpu"})
    assert not spawned
    # This process (the CPU test rig) holds no accelerator.
    assert platform.holds_accelerator() is False


# ------------------------------------- built from what git would commit


def test_native_binary_is_keyed_to_its_source():
    """Whatever mtimes a copy or a checkout left, the binary that runs was
    built from this exact source text."""
    with open(os.path.join(os.path.dirname(native.__file__),
                           "kvstore.cpp"), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    assert os.path.basename(native.kvstore_binary()) == (
        f"tpu_kvstore-{digest}"
    )
