#!/usr/bin/env python
"""Diagnose the runtime environment (reference: tools/diagnose.py —
prints platform / framework / hardware / connectivity info for bug
reports). The TPU build reports the JAX/XLA stack and device topology
instead of the reference's CUDA probes; there is no network section
(deployments are airgapped pods more often than not).

Run: python tools/diagnose.py
"""
import os
import platform
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def section(title):
    print("----------" + title + "----------", flush=True)


def main():
    section("Python Info")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())

    section("Platform Info")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("release      :", platform.release())
    print("version      :", platform.version())
    print("processor    :", platform.processor() or "n/a")
    print("cpu count    :", os.cpu_count())

    section("Environment")
    for k in sorted(os.environ):
        if k.startswith(("MXNET_", "JAX_", "XLA_", "TPU_", "LIBTPU_")):
            print("%s=%s" % (k, os.environ[k]))

    section("Framework Info")
    t0 = time.time()
    import mxnet_tpu as mx
    print("mxnet_tpu    :", mx.__version__)
    print("import time  : %.3fs" % (time.time() - t0))
    print("location     :", os.path.dirname(os.path.abspath(mx.__file__)))
    from mxnet_tpu.libinfo import find_lib_path
    print("native libs  :", find_lib_path() or "(not built)")
    from mxnet_tpu.ops.registry import list_ops
    print("ops          :", len(list_ops()))

    section("JAX / XLA Info")
    import jax
    import jaxlib
    print("jax          :", jax.__version__)
    print("jaxlib       :", jaxlib.__version__)

    section("Device Info")
    # one in-process enumeration: the chip belongs to one process, so
    # no probe child may claim it first
    t0 = time.time()
    devs = jax.devices()
    print("platform     :", devs[0].platform)
    print("device_kind  :", devs[0].device_kind)
    print("devices      :", [str(d) for d in devs])
    print("counts       : %d global, %d local, %d process(es)"
          % (jax.device_count(), jax.local_device_count(),
             jax.process_count()))
    print("enumeration  : %.1fs" % (time.time() - t0))
    if devs[0].platform == "cpu":
        print("note         : no accelerator attached; running on "
              "host CPU")


if __name__ == "__main__":
    main()
