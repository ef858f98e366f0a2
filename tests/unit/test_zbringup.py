"""The loud behaviour of the chip path, checked on the CPU mesh: entry
points that refuse to measure without a TPU, a compile cache that can be
placed from outside, kernels whose errors propagate once a guard has said
yes, and a launcher that will not hand one chip to several processes.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(args, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, **env)
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=full,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_entry_points_fail_without_a_tpu(script):
    """Non-zero exit and a message naming the missing TPU, before any
    model is built (neither script has imported the package yet) and
    with no result line on stdout."""
    proc = _run([script])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "platform == 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""
    assert "deepspeed_tpu" not in proc.stderr      # no engine log line


_CACHE_PROBE = """
import json, os, sys
import jax
from deepspeed_tpu.utils.compile_cache import enable_compile_cache
before = jax.config.jax_compilation_cache_dir
path = enable_compile_cache()
jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8.0)).block_until_ready()
print(json.dumps({"before": before, "path": path,
                  "after": jax.config.jax_compilation_cache_dir}))
"""


def test_compile_cache_placed_from_outside(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the helper leaves the
    directory alone (JAX read the variable itself) and entries land only
    under it."""
    placed = tmp_path / "placed"
    proc = _run(["-c", _CACHE_PROBE], JAX_COMPILATION_CACHE_DIR=str(placed))
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["before"] == out["after"] == out["path"] == str(placed)
    assert os.listdir(placed), "no cache entry written under the placed dir"
    assert not os.path.exists(os.path.join(ROOT, ".jax_cache", "placed"))


def test_compile_cache_default_is_one_in_checkout_path():
    """Unset, two fresh processes agree on ``<checkout>/.jax_cache`` —
    nothing in the path comes from a temporary name, a pid or the clock."""
    probe = ("from deepspeed_tpu.utils.compile_cache import "
             "enable_compile_cache; print(enable_compile_cache())")
    paths = []
    for _ in range(2):
        proc = _run(["-c", probe])
        assert proc.returncode == 0, proc.stderr
        paths.append(proc.stdout.strip().splitlines()[-1])
    assert paths[0] == paths[1] == os.path.join(ROOT, ".jax_cache")


def test_a_run_does_not_evict_its_own_entries_from_a_capped_cache(tmp_path):
    """Under a size cap JAX's cache evicts the least recently used entries
    for a new one; ``enable_compile_cache`` makes it leave out an entry that
    does not fit beside what this process has read or written and is larger
    than all of that: it would evict them, the next run would write them
    over it, and neither would ever hit (PR 60).  A smaller one is kept and
    evicts as before, whichever came first; another process's entries are
    no reason to refuse; without a cap nothing is refused."""
    from jax._src.lru_cache import LRUCache

    from deepspeed_tpu.utils import compile_cache

    assert compile_cache._leave_room() and compile_cache._leave_room()
    cache = LRUCache(str(tmp_path / "capped"), max_size=1000)
    cache.put("small-a", b"a" * 200)
    cache.put("small-b", b"b" * 200)
    cache.put("large", b"c" * 700)          # 700 + 400 > 1000, 700 > 400
    assert cache.get("large") is None
    assert cache.get("small-a") == b"a" * 200
    cache.put("third", b"d" * 350)          # fits beside them: kept
    cache.put("fourth", b"e" * 350)         # does not, but is the smaller
    assert cache.get("fourth") == b"e" * 350 and cache.get("small-b") is None
    # the larger part first: it is kept until the smaller parts need the room
    first = LRUCache(str(tmp_path / "first"), max_size=1000)
    first.put("large", b"c" * 700)
    first.put("small-a", b"a" * 200)
    first.put("small-b", b"b" * 200)
    assert first.get("large") is None and first.get("small-a") is not None
    # the next process of the same program: what it reads counts as used
    again = LRUCache(str(tmp_path / "first"), max_size=1000)
    assert again.get("small-a") and again.get("small-b")
    again.put("large", b"c" * 700)
    assert again.get("large") is None and again.get("small-b") is not None
    # what another process left there is evicted as JAX does
    other = LRUCache(str(tmp_path / "first"), max_size=1000)
    other.put("large", b"c" * 700)
    assert other.get("large") == b"c" * 700 and other.get("small-a") is None
    free = LRUCache(str(tmp_path / "free"), max_size=-1)
    free.put("small", b"a" * 400)
    free.put("large", b"c" * 950)
    assert free.get("large") == b"c" * 950


@pytest.fixture()
def one_device_mesh():
    mesh_mod.set_mesh(mesh_mod.build_mesh({"dp": 1},
                                          devices=jax.devices()[:1]))
    yield
    mesh_mod.set_mesh(None)


def test_kernel_error_propagates_past_its_guard(monkeypatch, one_device_mesh):
    """A guard may choose the XLA path; once it has said yes, the
    kernel's exception reaches the caller instead of a reference result."""
    import importlib

    from deepspeed_tpu.models import common
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import decode_layer

    # the package re-exports the function under the module's name
    flash_attention = importlib.import_module(
        "deepspeed_tpu.ops.pallas.flash_attention")

    def boom(*a, **k):
        raise RuntimeError("mosaic refused")

    q = jnp.zeros((2, 128, 2, 64), jnp.float32)
    monkeypatch.setattr(flash_attention, "flash_attention", boom)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        attention.dot_product_attention(q, q, q, impl="flash")

    monkeypatch.setattr(decode_layer, "fused_norm_proj", boom)
    monkeypatch.setattr(decode_layer, "fused_post_attn", boom)
    x = jnp.zeros((1, 128), jnp.float32)
    w = jnp.zeros((128, 384), jnp.float32)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        common.fused_decode_qkv(x, jnp.ones(128), jnp.zeros(128), w, None,
                                rms=False, eps=1e-5, interpret=True)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        common.fused_decode_post_attn(
            x, x, jnp.zeros((128, 128)), None, jnp.ones(128),
            jnp.zeros(128), (w, None, w.T, None), interpret=True)


def test_dispatch_report_names_site_choice_and_reason(one_device_mesh):
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas.spmd import dispatch_report

    def rows():
        return {r[:3]: r[3] for r in dispatch_report()}

    before = rows()
    q = jnp.zeros((1, 16, 2, 64), jnp.float32)
    attention.dot_product_attention(q, q, q, impl="auto")
    key = ("attention", "jnp", "auto: not a TPU")
    assert rows().get(key, 0) == before.get(key, 0) + 1


def test_launcher_refuses_to_share_chips(monkeypatch):
    from deepspeed_tpu.launcher import runner

    monkeypatch.setattr(runner, "_local_tpu_chips", lambda: 4)
    for k in ("JAX_PLATFORMS", *runner._CHIP_VISIBILITY_ENVS):
        monkeypatch.delenv(k, raising=False)
    runner._refuse_shared_chips(1)                 # one process: fine
    with pytest.raises(SystemExit, match="one process"):
        runner._refuse_shared_chips(2)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")     # children stay off them
    runner._refuse_shared_chips(2)
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")   # caller took charge
    runner._refuse_shared_chips(2)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS")
    monkeypatch.setattr(runner, "_local_tpu_chips", lambda: 0)
    runner._refuse_shared_chips(2)                 # no chips: emulation
