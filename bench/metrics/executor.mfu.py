"""Model FLOP utilization of the whole run: the FLOPs one row needs
forward and backward (``bench/work/<config's work>.py``, nothing
recomputed) x rows per second / (chips x the bf16 peak). An END-TO-END
utilization, named as such: it is not a kernel's roofline share and says
nothing about idle time. Float32 convolutions run as bf16 passes on the
MXU at JAX's default precision, so the bf16 peak is the honest ceiling."""

LAYER = "Executor fused step"
UNIT = "%"
MOVES = "train_samples_per_s"
DRIVERS = ("fit_cli",)


def read(run):
    if run.peaks is None:
        return None
    work = run.work(run.config["work"])
    image = tuple(int(x) for x in
                  run.config["cli_flags"]["image-shape"].split(","))
    flops = work.train_flops_per_row(run.samples["param_shapes"], image)
    rate = run.samples["rate"]      # rows a second over the window
    return 100.0 * flops * rate / (run.chips * run.peaks["bf16_flops_per_s"])
