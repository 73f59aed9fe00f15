"""What a host without a TPU must see (chip_smoke.py's contract, the device
rules it stands on, and where the compile caches live).  The smoke itself
only passes on the chip; here every piece of it that is a refusal is held
to refusing."""
import ast
import os
import re
import shutil
import subprocess
import sys

import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu.analysis import costmodel
from mxnet_tpu.compile import paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, cwd=REPO, env=None, timeout=300):
    return subprocess.run([sys.executable] + argv, cwd=cwd, text=True,
                          capture_output=True, timeout=timeout,
                          env=dict(os.environ, **(env or {})))


def test_chip_smoke_refuses_a_host_without_a_tpu_and_says_why():
    r = _run(["chip_smoke.py"])
    assert r.returncode not in (0, None)
    assert "platform=cpu" in r.stdout.splitlines()[0]
    assert "found no TPU" in r.stderr
    # no result line: nothing on stdout parses as the verdict object
    for line in r.stdout.splitlines():
        assert not line.startswith("{"), line


def test_chip_smoke_alone_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       text=True, capture_output=True, timeout=300, env=env)
    assert r.returncode not in (0, None)
    assert "mxnet_tpu" in r.stderr
    assert r.stdout.strip() == ""


def test_smoke_reads_mosaic_kernels_and_callbacks_out_of_program_text():
    sys.path.insert(0, REPO)
    import chip_smoke
    text = "\n".join([
        '  %jvp_flash_fwd_.2 = (bf16[96,1024,64]{2,1,0}, f32[96,1024,128]) '
        'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call", '
        'backend_config={}',
        '  %flash_bwd_dq.3 = bf16[96,1024,64]{2,1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call"',
        '  %cc = f32[8] custom-call(%x), custom_call_target="Sharding"',
    ])
    assert chip_smoke.mosaic_kernels(text) == ["jvp_flash_fwd_.2",
                                               "flash_bwd_dq.3"]
    chip_smoke.check_no_interpreter(text, "program")
    with pytest.raises(AssertionError, match="xla_python_cpu_callback"):
        chip_smoke.check_no_interpreter(
            text + '\n  %cb = f32[8] custom-call(%x), '
            'custom_call_target="xla_python_cpu_callback"', "program")


def test_accelerator_contexts_raise_where_there_is_none():
    assert all(d.platform == "cpu" for d in jax.local_devices())
    for ctx in (mx.tpu(0), mx.gpu(0), mx.tpu(3)):
        with pytest.raises(mx.MXNetError, match="names accelerator"):
            ctx.jax_device
    assert mx.cpu(0).jax_device.platform == "cpu"
    assert mx.context.num_tpus() == 0


def test_benchmark_refuses_to_time_a_cpu():
    """The benchmark of record runs nothing where there is no TPU, and has
    no peaks for a device kind nobody published any for."""
    r = _run(["benchmark/run.py", "--workload", "gpt2s.serve-closed32",
              "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert r.returncode == 2
    assert "needs 1 TPU chip(s)" in r.stderr and "nothing was run" in r.stderr
    assert r.stdout.strip() == ""
    from benchmark.lib import peaks
    assert peaks.chip_peaks("TPU v5 lite")["flops"] == 197e12
    for kind in ("cpu", "TPU v9000", ""):
        with pytest.raises(KeyError, match="no published peaks"):
            peaks.chip_peaks(kind)


def test_launcher_refuses_tpu_ranks():
    r = _run(["tools/launch.py", "-n", "2", "--dist-device", "tpu", "--",
              sys.executable, "-c", "pass"])
    assert r.returncode == 2
    assert "a chip belongs to one process" in r.stderr


def test_chip_peaks_are_keyed_by_device_kind():
    v5e = costmodel.chip_peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bytes_s"] == 819e9
    for kind in ("cpu", "TPU v9000", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            costmodel.chip_peaks(kind)
    # and nothing defaults to one: the roofline wants its peaks named
    with pytest.raises(TypeError):
        costmodel.roofline(1e9, 1e6, 0.0)


# ---------------------------------------------------------------------------
# where the compile caches live
# ---------------------------------------------------------------------------

_PRINT_CACHE_DIR = ("import jax, mxnet_tpu; "
                    "print(jax.config.jax_compilation_cache_dir)")


def test_jax_cache_follows_the_variable_when_set(tmp_path):
    r = _run(["-c", _PRINT_CACHE_DIR],
             env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "placed")})
    assert r.returncode == 0, r.stderr[-1000:]
    assert r.stdout.strip() == str(tmp_path / "placed")


def test_jax_cache_defaults_to_one_fixed_directory_in_the_checkout(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    # run from somewhere else: the path follows the checkout, not the cwd
    r = subprocess.run([sys.executable, "-c", _PRINT_CACHE_DIR],
                       cwd=str(tmp_path), text=True, capture_output=True,
                       timeout=300, env=dict(env, PYTHONPATH=REPO))
    assert r.returncode == 0, r.stderr[-1000:]
    assert r.stdout.strip() == os.path.join(REPO, ".cache", "jax")
    assert paths.jax_cache_dir() == os.path.join(REPO, ".cache", "jax")
    # git ignores it
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".cache/" in f.read().split()


def test_nothing_else_sets_the_cache_directory():
    setters = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d not in (".git", ".cache", "scratch", "chiprun_out",
                                "__pycache__", "build")]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            if os.path.samefile(path, __file__):
                continue
            with open(path, errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if "compilation_cache_dir" in line and (
                            "update(" in line or "environ[" in line
                            or "setdefault(" in line):
                        setters.append("%s:%d" % (
                            os.path.relpath(path, REPO), i))
    assert setters == ["mxnet_tpu/__init__.py:%d" % _init_line()], setters


def _init_line():
    with open(os.path.join(REPO, "mxnet_tpu", "__init__.py")) as f:
        lines = f.read().splitlines()
    (i,) = [i for i, line in enumerate(lines, 1)
            if "jax_compilation_cache_dir" in line]
    # guarded by the variable, on the line before
    assert 'environ.get("JAX_COMPILATION_CACHE_DIR")' in lines[i - 2]
    return i


def test_layout_carrying_programs_stay_outside_the_jax_cache():
    """An executable out of jax's persistent cache forgets non-default
    result layouts on this installation (PERF.md, PR 21), so the
    AUTO-layout step and its re-lay programs compile in a scope where the
    cache is off — and only there."""
    from mxnet_tpu.compile import outside_jax_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        with outside_jax_cache():
            assert not jax.config.jax_enable_compilation_cache
        assert jax.config.jax_enable_compilation_cache
        with pytest.raises(KeyError):
            with outside_jax_cache():
                raise KeyError("restored on the way out of an error too")
        assert jax.config.jax_enable_compilation_cache
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    import inspect
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    assert "outside_jax_cache()" in inspect.getsource(
        ShardedTrainer.build_step_auto_layout)


def test_own_caches_default_inside_the_checkout(monkeypatch):
    for name in ("MXNET_TPU_AUTOTUNE_CACHE", "MXNET_TPU_COMPILE_CACHE",
                 "MXNET_TPU_CALIBRATION_CACHE"):
        monkeypatch.delenv(name, raising=False)
    from mxnet_tpu.analysis import predict
    from mxnet_tpu.compile import cache
    from mxnet_tpu.ops import autotune
    root = os.path.join(REPO, ".cache")
    assert paths.cache_root() == root
    home = os.path.expanduser("~")
    for p in (autotune.cache_path(), cache.cache_dir(),
              predict.calibration_store_path()):
        assert p.startswith(root + os.sep), p
        assert not p.startswith(os.path.join(home, ".cache")), p


def _code_strings(path):
    """Every string constant of a Python file that is not a docstring."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    docstrings = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            first = node.body[0] if node.body else None
            if isinstance(first, ast.Expr) and isinstance(
                    first.value, ast.Constant):
                docstrings.add(id(first.value))
    return tree, [n.value for n in ast.walk(tree)
                  if isinstance(n, ast.Constant) and isinstance(n.value, str)
                  and id(n) not in docstrings]


def _python_files(*tops):
    for top in tops:
        for root, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                if name.endswith(".py"):
                    yield os.path.join(root, name)


def test_repo_ledger_never_uses_the_drivers_file_name():
    """PERF_LEDGER.jsonl at the root is the PR driver's record and the only
    one: no code of the library or the tools holds its name as a path, and
    the library loads nothing from tools/ (a tool may import the library,
    never the other way round)."""
    for path in _python_files("mxnet_tpu", "tools"):
        rel = os.path.relpath(path, REPO)
        tree, strings = _code_strings(path)
        assert not [s for s in strings if "PERF_LEDGER.jsonl" in s], rel
        if not rel.startswith("mxnet_tpu"):
            continue
        for node in ast.walk(tree):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom)
                     and not node.level else [])
            assert not [n for n in names if n.split(".")[0] == "tools"], rel
        # a path component of its own, or a literal that is a tool's path
        assert not [s for s in strings if s == "tools"
                    or re.fullmatch(r"(.*/)?tools/\w+\.py", s)], rel
