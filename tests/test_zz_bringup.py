"""Start-up and device-selection contract (ISSUE 21): contexts never
fall back quietly, importing the package claims no device, and
``chip_smoke.py`` refuses to mean anything on a CPU.

Named to sort last: the rehearsal is the slowest test here and nothing
else depends on it.
"""
import json
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu import context as ctx_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env=None, timeout=600):
    args = (["-c", code_or_args] if isinstance(code_or_args, str)
            else code_or_args)
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, cwd=REPO,
                          env=env or dict(os.environ))


# ---------------------------------------------------------------------------
# contexts
# ---------------------------------------------------------------------------

def test_forced_cpu_keeps_the_eight_device_fake():
    import jax
    assert ctx_mod.platform_forced_cpu()
    devs = [mx.tpu(i).jax_device() for i in range(8)]
    assert devs == jax.local_devices() and len(set(devs)) == 8


def test_tpu_id_out_of_range_raises_instead_of_wrapping():
    for bad in (8, 11, -1):
        with pytest.raises(mx.MXNetError, match="has 8 device"):
            mx.tpu(bad).jax_device()
    with pytest.raises(mx.MXNetError):
        mx.nd.zeros((2,), ctx=mx.tpu(8))
    # host contexts share one memory: they still wrap
    assert mx.cpu(9).jax_device() == mx.cpu(1).jax_device()


def test_no_accelerator_and_platform_not_forced_raises(monkeypatch):
    monkeypatch.setattr(ctx_mod, "platform_forced_cpu", lambda: False)
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        mx.tpu(0).jax_device()
    assert mx.cpu(0).jax_device().platform == "cpu"     # host stays host


def test_platform_forced_reads_the_jax_config(monkeypatch):
    import jax

    class _Cfg:
        jax_platforms = None

    monkeypatch.setattr(jax, "config", _Cfg)
    for value, forced in ((None, False), ("", False), ("tpu,cpu", False),
                          ("cpu", True), (" CPU ", True)):
        _Cfg.jax_platforms = value
        assert ctx_mod.platform_forced_cpu() is forced, value


def test_creation_ops_commit_to_their_destination_context():
    """What hid the chip: an initializer filling a tpu() array computed
    under the default cpu(0) context and left the weight on the host."""
    dev = mx.tpu(3).jax_device()
    arr = mx.nd.zeros((4, 5), ctx=mx.tpu(3))
    mx.init.Xavier()(mx.init.InitDesc("fc_weight"), arr)
    assert arr._data.devices() == {dev} and arr._data.committed
    arr[:] = 1.0                                   # fill: stays put
    assert arr._data.devices() == {dev} and arr._data.committed
    mx.nd.ones((4, 5)).copyto(arr)                 # host -> device
    assert arr._data.devices() == {dev}
    host = mx.nd.ones((2,))
    assert host._data.devices() == {mx.cpu(0).jax_device()}


# ---------------------------------------------------------------------------
# one process per chip: importing claims nothing
# ---------------------------------------------------------------------------

def test_import_initialises_no_backend_and_places_the_default_cache():
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR")           # tests/conftest.py set it
    r = _run("import mxnet_tpu, mxnet_tpu.serve.fleet, mxnet_tpu.serve.router,"
             " mxnet_tpu.module, mxnet_tpu.gluon,"
             " mxnet_tpu.kvstore, mxnet_tpu.parallel, mxnet_tpu.programs\n"
             "import jax\n"
             "from jax._src import xla_bridge\n"
             "print(bool(xla_bridge._backends))\n"
             "print(jax.config.jax_compilation_cache_dir)\n", env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    initialised, cache = r.stdout.split()[-2:]
    assert initialised == "False"
    assert cache == os.path.join(REPO, ".jax_cache")


def test_second_claimant_of_the_chip_gets_an_explanation(monkeypatch):
    """libtpu fails the second process at once, but with advice to
    delete its lockfile; the context layer says what actually happened."""
    import jax

    def held():
        raise RuntimeError(
            "Unable to initialize backend 'tpu': ABORTED: Internal error "
            "when accessing libtpu multi-process lockfile. Run \"$ sudo rm "
            "/tmp/libtpu_lockfile\".")

    monkeypatch.setattr(jax, "devices", held)
    with pytest.raises(mx.MXNetError, match="held by another process.*"
                       "TPU_VISIBLE_CHIPS"):
        mx.tpu(0).jax_device()


# ---------------------------------------------------------------------------
# kernels: the real Mosaic compiler, no chip (tools/check_mosaic_aot.py)
# ---------------------------------------------------------------------------

def test_every_exported_kernel_compiles_for_the_v5e():
    r = _run(["tools/check_mosaic_aot.py"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-1000:]
    assert "every kernel compiles for the v5e" in r.stdout
    assert "compiling for TPU v5 lite" in r.stdout


@pytest.fixture(scope="module")
def pool_in_place():
    """``tools/check_pool_in_place.py``, once: the serving programs of a
    small paged model compiled for a described v5e, TPU branches taken."""
    r = _run(["tools/check_pool_in_place.py"])
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    return r, {(ln["config"], ln["program"]): ln for ln in lines}


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("config", ["one_pool", "pool_a_kind"])
def test_serving_program_leaves_the_pool_in_place(pool_in_place, config,
                                                  program):
    """No slice, copy or fusion outside the Mosaic calls makes an array
    of one layer's pool shape, both pools are aliased argument -> result
    and the temporaries are smaller than a layer: the kernels address
    the layer themselves. (A ``pool[layer]`` in front of a custom call
    is a copy: two a layer in each program before the kernels took the
    layer's index.)"""
    r, lines = pool_in_place
    assert (config, program) in lines, r.stdout[-2000:] + r.stderr[-2000:]
    line = lines[config, program]
    assert line["mosaic_calls"] >= 3
    assert line["layer_copies"] == 0, r.stdout[-3000:]
    assert line["aliased_bytes"] >= line["pool_bytes"]
    assert line["temp_bytes"] < line["layer_bytes"]


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

def test_chip_smoke_refuses_on_cpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "found no accelerator" in r.stderr and "--tiny-cpu" in r.stderr
    # no result is printed: the last stdout line is not the JSON verdict
    assert '"ok"' not in r.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    r = subprocess.run([sys.executable, "chip_smoke.py", "--tiny-cpu"],
                       capture_output=True, text=True, timeout=120,
                       cwd=str(tmp_path),
                       env={k: v for k, v in os.environ.items()
                            if k != "PYTHONPATH"})
    assert r.returncode != 0 and '"ok"' not in r.stdout
    assert "cannot import the program" in r.stderr


def test_chip_smoke_rehearsal_runs_green():
    r = _run(["chip_smoke.py", "--tiny-cpu"])
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    verdict = json.loads(lines[-1])
    assert verdict["ok"] is True and verdict["rehearsal"] is True
    assert verdict["device"]["platform"] == "cpu"
    out = r.stdout
    assert "proves NOTHING about the device" in out
    for phase in ("train", "kernels", "trace_clock", "dp4"):
        assert "phase %-8s passed" % phase in out, out[-2000:]
    # the clock tracing.py's docs name for the xplane's host events
    assert "xplane host events are on ['time.time_ns']" in out
