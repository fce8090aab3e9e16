"""``route_accumulate``'s share of its roofline: the least time the
window's real tuples need on the chip (the larger of bytes over HBM
bandwidth and operations over the int8 peak, counted by
``bench/roofline/route_accumulate.py``) over the kernel's device time in
the trace.  Where the trace names no such kernel, the device time of the
lane-scan programs stands in (``kernel_source`` in the trace reduction
says which)."""

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    k = tr["kernels"].get("route_accumulate")
    if not k or not k["seconds"]:
        return None
    spec = importlib.util.spec_from_file_location(
        "roofline_route_accumulate", BENCH / "roofline" / "route_accumulate.py")
    roof = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roof)
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if ctx["device_kind"] not in peaks:
        raise KeyError(f"no peaks for device {ctx['device_kind']!r} in "
                       "bench/peaks.json")
    p = peaks[ctx["device_kind"]]
    ops, nbytes = roof.work(ctx["rows"])
    least = max(nbytes / p["hbm_bytes_per_s"], ops / p["int8_ops_per_s"])
    # the kernel time is summed over the chips used; so is the work
    return 100.0 * least / k["seconds"]
