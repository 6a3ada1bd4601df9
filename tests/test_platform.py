"""Platform selection and compile-cache placement (utils/platform.py).

The contract: an explicit request wins; JAX's own fallback to the CPU is
an error unless the CPU was asked for; the compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says or to one fixed path in the checkout.
"""

import os
import subprocess
import sys

import jax
import pytest

from distributed_llm_inferencing_tpu.utils import platform as plat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def pinned(monkeypatch):
    """Record force_platform calls instead of re-pinning this process."""
    calls = []
    monkeypatch.setattr(plat, "force_platform", calls.append)
    monkeypatch.setattr(plat, "enable_compilation_cache", lambda: None)
    return calls


@pytest.mark.parametrize("arg,env,jax_env,asked,forced", [
    ("cpu", None, None, "cpu", ["cpu"]),          # --platform
    ("cpu", "tpu", "tpu", "cpu", ["cpu"]),        # the argument wins
    (None, "cpu", None, "cpu", ["cpu"]),          # DLI_PLATFORM
    (None, None, "cpu", "cpu", []),               # JAX's own variable
    (None, None, None, None, []),                 # JAX's default
])
def test_explicit_platform_wins(monkeypatch, pinned, arg, env, jax_env,
                                asked, forced):
    for name, val in (("DLI_PLATFORM", env), ("JAX_PLATFORMS", jax_env)):
        if val is None:
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, val)
    assert plat.pin_platform(arg) == asked
    assert pinned == forced


def test_cpu_default_raises_unless_cpu_was_asked(monkeypatch):
    """JAX drops to the CPU when it finds no chip; that is an error,
    never a quiet CPU run."""
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with pytest.raises(plat.BackendUnavailable, match="not requested"):
        plat.check_backend(None)
    with pytest.raises(plat.BackendUnavailable):
        plat.check_backend("tpu")      # asked for a chip, got the cpu
    assert plat.check_backend("cpu") == "cpu"
    assert plat.check_backend("tpu,cpu") == "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert plat.check_backend(None) == "tpu"


def test_unavailable_requested_backend_raises(monkeypatch):
    """Whatever JAX raises for a platform it cannot initialize passes
    through — nothing catches it and pins the CPU instead."""
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        plat.check_backend("tpu")


def test_cache_dir_left_to_jax_when_env_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    assert plat.enable_compilation_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_cache_dir_fixed_in_checkout_when_env_unset(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    first = plat.enable_compilation_cache()
    assert first == plat.enable_compilation_cache()      # two calls
    assert first == os.path.join(REPO, ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == first
    # and two processes: nothing in the path comes from a pid, a clock
    # or a temporary name
    code = ("from distributed_llm_inferencing_tpu.utils import platform;"
            "print(platform.enable_compilation_cache())")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    other = [subprocess.run([sys.executable, "-c", code], env=env,
                            cwd=cwd, capture_output=True, text=True,
                            check=True).stdout.strip()
             for cwd in (REPO, "/")]
    assert other == [first, first]


@pytest.mark.parametrize("argv", [
    ["-m", "distributed_llm_inferencing_tpu", "worker", "--port", "0"],
    ["-m", "distributed_llm_inferencing_tpu", "generate", "--prompt", "x",
     "--allow_random_init", "--model_name", "tiny-llama"],
    ["-m", "distributed_llm_inferencing_tpu.runtime.worker", "--port", "0"],
    ["bench.py"],
])
def test_entry_points_refuse_an_unrequested_cpu(argv, tmp_path):
    """No chip and no cpu request: exit non-zero, no result on stdout.
    The child sees a machine without libtpu (a stub that fails to import
    shadows it — loading the real one here would take libtpu's lock away
    from tests/test_tpu_compile.py), so JAX's default is the CPU, which
    nobody requested."""
    (tmp_path / "libtpu.py").write_text(
        "raise ImportError('hidden by tests/test_platform.py')\n")
    env = {k: v for k, v in os.environ.items()
           if k not in ("DLI_PLATFORM", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = f"{tmp_path}{os.pathsep}{REPO}"
    r = subprocess.run([sys.executable, *argv], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "cpu was not requested" in r.stderr


def test_master_stays_off_the_backend():
    """The control plane never initializes a JAX backend (it shares a
    host with the worker that owns the chip)."""
    code = ("import sys; "
            "from distributed_llm_inferencing_tpu.runtime.master import "
            "Master; m = Master(':memory:'); "
            "import jax._src.xla_bridge as xb; "
            "assert not xb._backends, xb._backends")
    env = dict(os.environ, PYTHONPATH=REPO)
    subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                   check=True, timeout=120)
