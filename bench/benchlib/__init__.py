"""The benchmark's own library: everything a measurement depends on that
the program under test must not be able to change — the workload
generator, the trace reduction, the FLOP/byte counts, the peak table, the
plain reference and the comparison that decides ``correct``."""

import importlib.util
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(path: str, name: str):
    """The Python file at ``path``, imported as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_named(part: str, name: str, bench: str = None):
    """``<bench>/<part>/<name>.py``: a driver, a data recipe or a metric
    reader, found by the name a cell or a configuration gives it
    (``bench`` defaults to this benchmark's directory)."""
    bench = bench or BENCH
    path = os.path.join(bench, part, name + ".py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(os.path.join(bench, part))
                       if f.endswith(".py"))
        raise ValueError(f"no {part}/{name}.py (known: {known})")
    return load_module(path, "bench_" + part + "_"
                       + name.replace("-", "_").replace(".", "_"))
