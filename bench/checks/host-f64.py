"""``host-f64``: the plain reference replayed in numpy float64 on the
host (``benchlib.elref`` with the configuration's ``configs/<name>.py``:
``init``, ``local_step(P, ...)``, ``metric(P, ...)``), and its control,
the same reference with float32 values and one-pass bfloat16 products
(``benchlib.prec``'s ``default``).  For a model whose every step a host
can follow: the SVM and K-means."""

from benchlib import data, elref
from benchlib.prec import DEFAULT, F64


def workload(cfg: dict, ref, control: bool = False) -> elref.Workload:
    edges, test = data.make(cfg)
    return elref.Workload(cfg, ref, edges, test, DEFAULT if control else F64)
