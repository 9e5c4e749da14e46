"""Pip runtime envs: venv-backed per-env worker pools (offline-safe —
installs a local package path, no index access)."""

import os
import shutil
import subprocess
import textwrap

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def local_pkg(tmp_path_factory):
    """A minimal installable package at a local path."""
    root = tmp_path_factory.mktemp("pkg") / "tpu_testpkg"
    (root / "tpu_testpkg").mkdir(parents=True)
    (root / "tpu_testpkg" / "__init__.py").write_text(
        "MAGIC = 'runtime-env-works'\n")
    (root / "pyproject.toml").write_text(textwrap.dedent("""\
        [build-system]
        requires = ["setuptools"]
        build-backend = "setuptools.build_meta"

        [project]
        name = "tpu-testpkg"
        version = "0.1"
    """))
    return str(root)


def test_env_key_stability():
    from ray_tpu.core.runtime_env_manager import env_key

    assert env_key(None) is None
    assert env_key({"env_vars": {"A": "1"}}) is None
    k1 = env_key({"pip": ["b", "a"]})
    assert k1 == env_key({"pip": ["a", "b"]})
    assert k1 != env_key({"pip": ["a"]})
    assert env_key({"pip": {"packages": ["a", "b"]}}) == k1


@pytest.mark.slow
def test_pip_runtime_env_task(ray_start_regular, local_pkg):
    @ray_tpu.remote
    def probe():
        import tpu_testpkg

        return tpu_testpkg.MAGIC, tpu_testpkg.__file__

    # no runtime env: the package must NOT be importable
    with pytest.raises(Exception, match="tpu_testpkg"):
        ray_tpu.get(probe.remote(), timeout=120)

    r = probe.options(
        runtime_env={"pip": ["--no-index", "--no-build-isolation", local_pkg]}
    ).remote()
    magic, path = ray_tpu.get(r, timeout=300)
    assert magic == "runtime-env-works"
    assert "/runtime_envs/" in path  # imported from the venv, not base site


@pytest.mark.slow
def test_pip_runtime_env_actor(ray_start_regular, local_pkg):
    @ray_tpu.remote
    class EnvActor:
        def probe(self):
            import tpu_testpkg

            return tpu_testpkg.MAGIC

    a = EnvActor.options(runtime_env={
        "pip": ["--no-index", "--no-build-isolation", local_pkg]}).remote()
    assert ray_tpu.get(a.probe.remote(), timeout=300) == "runtime-env-works"
    ray_tpu.kill(a)


@pytest.mark.slow
def test_pip_runtime_env_failure_propagates(ray_start_regular):
    from ray_tpu.core.exceptions import RuntimeEnvSetupError

    @ray_tpu.remote
    def never_runs():
        return 1

    r = never_runs.options(runtime_env={
        "pip": ["--no-index", "/nonexistent/definitely-not-a-package"]}).remote()
    with pytest.raises(RuntimeEnvSetupError):
        ray_tpu.get(r, timeout=300)


def test_py_modules_shipping(ray_start_regular, tmp_path):
    """py_modules (reference packaging.py): a local module zips into a
    content-addressed KV package, workers extract and import it."""
    pkg = tmp_path / "shipme"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("MAGIC = 'shipped-427'\n")
    (pkg / "helper.py").write_text("def triple(x):\n    return 3 * x\n")

    @ray_tpu.remote
    def use_module():
        import shipme
        from shipme.helper import triple

        return shipme.MAGIC, triple(9)

    magic, got = ray_tpu.get(use_module.options(
        runtime_env={"py_modules": [str(pkg)]}).remote())
    assert magic == "shipped-427" and got == 27

    # actors get it too
    @ray_tpu.remote
    class Uses:
        def __init__(self):
            import shipme

            self.magic = shipme.MAGIC

        def get(self):
            return self.magic

    a = Uses.options(runtime_env={"py_modules": [str(pkg)]}).remote()
    assert ray_tpu.get(a.get.remote()) == "shipped-427"


def test_third_party_plugin_registers_and_builds(tmp_path):
    """VERDICT done-criterion: a third-party runtime-env plugin is
    registrable and drives create/modify_context through the manager."""
    from ray_tpu.core.runtime_env_manager import (
        EnvContext, RuntimeEnvManager, RuntimeEnvPlugin, env_key,
        register_plugin, unregister_plugin)

    calls = []

    class TouchPlugin(RuntimeEnvPlugin):
        name = "touch"

        def key_spec(self, value):
            return sorted(value)

        def create(self, value, env_dir):
            calls.append(("create", tuple(sorted(value))))
            os.makedirs(env_dir, exist_ok=True)
            with open(os.path.join(env_dir, "touched"), "w") as f:
                f.write(",".join(value))

        def modify_context(self, value, env_dir, ctx: EnvContext):
            calls.append(("context", env_dir))
            ctx.env_vars["TOUCHED"] = "1"

    register_plugin(TouchPlugin())
    try:
        mgr = RuntimeEnvManager(base_dir=str(tmp_path))
        env = {"touch": ["a", "b"]}
        key = env_key(env)
        assert key is not None  # pooled plugin => dedicated worker pool key
        py = mgr.python_for(env)
        assert py  # context default: host interpreter
        assert os.path.exists(os.path.join(str(tmp_path), key, "touched"))
        assert ("create", ("a", "b")) in calls
        # second resolve: cached, no second create
        n_creates = sum(1 for c in calls if c[0] == "create")
        mgr.python_for(env)
        assert sum(1 for c in calls if c[0] == "create") == n_creates
    finally:
        unregister_plugin("touch")
    assert env_key({"touch": ["a"]}) is None  # unregistered: key gone


def test_env_refcount_and_gc(tmp_path):
    """URI-style refcounting: envs deletable only at zero references."""
    from ray_tpu.core.runtime_env_manager import (RuntimeEnvManager,
                                                  env_key)

    mgr = RuntimeEnvManager(base_dir=str(tmp_path))
    key = env_key({"py_modules": ["x"]})
    env_dir = os.path.join(str(tmp_path), key)
    os.makedirs(env_dir)
    mgr.acquire(key)
    mgr.acquire(key)
    assert mgr.release(key) == 1
    assert mgr.gc() == []          # still referenced
    assert os.path.exists(env_dir)
    assert mgr.release(key) == 0
    assert mgr.gc() == [key]       # reclaimed at zero
    assert not os.path.exists(env_dir)


def test_conda_plugin_requires_conda(tmp_path):
    """Conda envs are supported behind the plugin API; without a conda
    binary the failure is a clear error (skips where conda exists)."""
    import shutil as _shutil

    import ray_tpu as _rt
    from ray_tpu.core.runtime_env_manager import RuntimeEnvManager

    if _shutil.which("conda") or _shutil.which("mamba"):
        pytest.skip("conda present: the no-conda error path can't run")
    mgr = RuntimeEnvManager(base_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="conda"):
        mgr.python_for({"conda": {"dependencies": ["pip"]}})


def test_worker_env_refcount_lifecycle(ray_start_regular, local_pkg):
    """A pip-env worker acquires its env's refcount on register and
    releases on exit."""
    import ray_tpu
    from ray_tpu.core import api as _api
    from ray_tpu.core.runtime_env_manager import env_key

    env = {"pip": ["--no-index", "--no-build-isolation", local_pkg]}

    @ray_tpu.remote
    def where():
        import sys

        return sys.executable

    path = ray_tpu.get(where.options(runtime_env=env).remote(), timeout=180)
    assert "/runtime_envs/" in path
    raylet = getattr(_api._node, "_raylet", None)
    if raylet is None:
        pytest.skip("in-process raylet not reachable from this fixture")
    key = env_key(env)
    assert raylet._env_manager._refs.get(key, 0) >= 1


def test_container_command_assembly():
    """Request shape for the container plugin, no daemon needed
    (reference _private/runtime_env/container.py)."""
    from ray_tpu.core.runtime_env_manager import build_container_command

    cmd = build_container_command(
        {"image": "rayproject/base:1.0", "run_options": ["--gpus=all"]},
        engine="docker", pkg_root="/opt/src", base_dir="/tmp/renvs")
    assert cmd[0:3] == ["docker", "run", "--rm"]
    assert "--network=host" in cmd
    assert "-v" in cmd and "/dev/shm:/dev/shm" in cmd
    assert "/opt/src:/opt/src:ro" in cmd
    assert "/tmp/renvs:/tmp/renvs" in cmd
    i = cmd.index("--env-file")
    assert cmd[i + 1] == "{ENVFILE}"
    assert cmd[-1] == "rayproject/base:1.0"  # image last, before worker argv
    assert cmd[-2] == "--gpus=all"           # user options precede image

    with pytest.raises(ValueError, match="image"):
        build_container_command({}, engine="docker", pkg_root="/x")


def test_container_plugin_context_and_pooling(tmp_path):
    """The plugin wraps the worker command, swaps the interpreter to the
    in-image python, pools workers per image, and refuses pip/conda
    combinations."""
    import shutil as _shutil

    from ray_tpu.core.runtime_env_manager import (ContainerPlugin,
                                                  EnvContext, env_key)

    plug = ContainerPlugin()
    ctx = EnvContext()
    # explicit engine skips PATH detection: assembly works daemon-free —
    # route through an executable that always exists
    spec = {"image": "img:1", "engine": _shutil.which("true") or "/bin/true",
            "python": "/usr/bin/python3.11"}
    plug.modify_context(spec, str(tmp_path), ctx)
    assert ctx.python == "/usr/bin/python3.11"
    assert ctx.command_prefix[1:3] == ["run", "--rm"]
    assert ctx.command_prefix[-1] == "img:1"

    # container envs get their own worker pools, keyed by normalized spec
    k1 = env_key({"container": {"image": "img:1"}})
    k2 = env_key({"container": {"image": "img:2"}})
    assert k1 and k2 and k1 != k2
    assert env_key({"container": "img:1"}) == env_key(
        {"container": {"image": "img:1"}})


def test_container_rejects_pip_combo(tmp_path):
    from ray_tpu.core.runtime_env_manager import RuntimeEnvManager

    mgr = RuntimeEnvManager(base_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="container"):
        mgr.context_for({"container": {"image": "x"}, "pip": ["requests"]})


def test_container_requires_engine(tmp_path):
    import shutil as _shutil

    if _shutil.which("docker") or _shutil.which("podman"):
        pytest.skip("container engine present: no-engine path can't run")
    from ray_tpu.core.runtime_env_manager import RuntimeEnvManager

    mgr = RuntimeEnvManager(base_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="docker or podman"):
        mgr.context_for({"container": {"image": "x"}})


def test_envfile_materialized_at_spawn(tmp_path, monkeypatch):
    """The raylet replaces {ENVFILE} with a real KEY=VALUE file and wraps
    the worker argv with the container prefix."""
    import subprocess

    from ray_tpu.core import raylet as raylet_mod

    captured = {}

    class FakeProc:
        pid = 4242

    def fake_popen(argv, env=None, **kw):
        captured["argv"] = argv
        captured["env"] = env
        return FakeProc()

    monkeypatch.setattr(subprocess, "Popen", fake_popen)

    class Shell:
        _launch_worker = raylet_mod.Raylet._launch_worker

        class _S:
            address = "127.0.0.1:1"

        _server = _S()
        gcs_address = "127.0.0.1:2"

        class _N:
            @staticmethod
            def hex():
                return "ab" * 14

        node_id = _N()

        def __init__(self):
            import threading

            self._lock = threading.Lock()
            self._starting = []
            self._starting_env = {}
            self._starting_envfile = {}
            self._spawn_us = {}

    sh = Shell()
    sh._launch_worker("python3", {"A": "1", "PATH": "/bin"},
                      command_prefix=["docker", "run", "--env-file",
                                      "{ENVFILE}", "img"])
    argv = captured["argv"]
    assert argv[:2] == ["docker", "run"]
    assert argv[4] == "img" and argv[5] == "python3"
    envfile = argv[argv.index("--env-file") + 1]
    assert envfile != "{ENVFILE}"
    content = open(envfile).read()
    assert "A=1" in content and "PATH=/bin" in content
    # the file is tracked for deletion at registration / startup-death
    # (the {ENVFILE} mkstemp used to leak)
    assert sh._starting_envfile[FakeProc.pid] == envfile
    import os

    os.unlink(envfile)
