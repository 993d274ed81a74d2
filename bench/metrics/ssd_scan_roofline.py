"""The ``ssd_scan`` kernel's share of its roofline.

Every launch of the Mamba-2 SSD kernel in the traced window is found by
what a TPU trace shows of it: a Pallas kernel runs as a TPU custom call
(the op's HLO text holds ``custom-call(``), and the SSD kernel's operands
are the head-major inputs ``x`` ``[B, H, S, P]`` and ``B``/``C``
``[B, S, N]`` in float32, at the configuration's ``H`` (Mamba-2 heads),
``P`` (head size) and ``N`` (state size).  Those shapes give what the
launch needs (``launch``, below); the least time the v5e could take for
it is the larger of operations over the FLOP peak and bytes over the HBM
peak.  The share is that least time, summed over launches, over the
launches' device time.  A custom call without those operands is another
kernel and is not counted; a window with no launch leaves the metric out.
"""

import re

from benchlib import counts, trace

KERNEL = " custom-call("


def launch(b, s, h, p, n, chunk):
    """One launch over ``b`` rows of ``s`` positions: the chunked dual
    form's products, ``C B^T`` once a chunk (one group) and per head the
    within-chunk ``(G * decay) X``, the state's contribution ``C state^T``
    and the state update ``X^T (B * decay)``; the bytes of reading ``x``,
    the cumulative decay, ``B`` and ``C`` once and writing ``y`` and the
    final state."""
    c = s // chunk
    flops = b * c * (2.0 * chunk * chunk * n
                     + h * (2.0 * chunk * chunk * p + 4.0 * chunk * p * n))
    bytes_ = 4.0 * (2 * b * s * h * p + b * h * s + 2 * b * s * n
                    + b * h * p * n)
    return {"flops": flops, "bytes": bytes_}


def _launch(text, cfg):
    h, p, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
               cfg["mamba_d_state"])
    dims = [tuple(int(x) for x in m.split(","))
            for m in re.findall(r"f32\[([0-9,]+)\]", text)]
    xs = [d for d in dims if len(d) == 4 and d[1] == h and d[3] == p]
    bc = [d for d in dims if len(d) == 3 and d[2] == n]
    if not xs or not bc:
        return None
    b, _, s, _ = max(xs, key=lambda d: d[2])
    return launch(b, s, h, p, n, min(cfg["mamba_chunk_size"], s))


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    meta = ctx.trace["meta"]
    evs = trace.kernel_events(ctx.trace["device"], meta, KERNEL, ctx.lo,
                              ctx.hi)
    least = spent = 0.0
    for s, e, name in evs:
        need = _launch(name + " " + meta.get(name, ""), ctx.cfg)
        if need is None:
            continue
        least += counts.roofline_seconds(need["flops"], need["bytes"],
                                         ctx.peak)[0]
        spent += (e - s) / 1e9
    return 100.0 * least / spent if spent > 0 else None
