"""``paged_decode_attention``'s share of its roofline over the traced part
of the window: the bytes the kernel needs (the keys and values of every
live context, its queries and outputs; ``bench/work/
paged_decode_attention.py``) over the HBM rate, over the kernel's measured
time. Bound: HBM.

The contexts are those of the requests' tokens made by decode steps inside
the traced part: token k of a request (k >= 1; its prefill made token 0)
attends ``prompt + k`` positions, and is placed at ``t_first + k x (t_done
- t_first) / (tokens - 1)`` — the engine stamps only a session's first and
last token, and steps are regular between them."""

LAYER = "Pallas kernels"
UNIT = "%"
MOVES = "serve_tpot_p95_ms"
DRIVERS = ("decode_open_loop",)


def read(run):
    window = run.samples["trace_host_window"]
    if run.trace is None or run.peaks is None or not window:
        return None
    kernel = run.work("paged_decode_attention")
    seconds, calls, _names = run.trace.seconds_matching(kernel.TRACE_NAME)
    if not calls:
        return None
    lo, hi = window
    contexts = []
    for r in run.samples["all_requests"]:
        if r.first is None or r.done is None or r.tokens < 2:
            continue
        gap = (r.done - r.first) / (r.tokens - 1)
        for k in range(1, r.tokens):
            if lo <= r.first + k * gap < hi:
                contexts.append(r.prompt_len + k)
    if not contexts:
        return None
    m = run.config["model"]
    head_dim = m["d_model"] // m["n_heads"]
    nbytes = m["n_layers"] * kernel.bytes_per_layer_step(
        contexts, m["n_heads"], m["n_heads"], head_dim,
        run.samples["kv_itemsize"])
    return 100.0 * kernel.roofline_seconds(nbytes, run.peaks) / seconds
