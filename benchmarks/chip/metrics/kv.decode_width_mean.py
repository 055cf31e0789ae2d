"""Mean page-table width, in positions per lane, that the fused decode
step gathered, over the steps from the end of set-up to the end of
serving: sum of w x dn_w over sum of dn_w, where dn_w is the end minus
the start of the engine's gauge ``decode_width_<w>_steps``."""
import re

WIDTH = re.compile(r"decode_width_(\d+)_steps$")


def read(rec):
    start, end = rec.gauges.get("start"), rec.gauges.get("end")
    if not start or not end:
        return None
    steps = {int(m.group(1)): end[k] - start.get(k, 0.0)
             for k in end for m in [WIDTH.match(k)] if m}
    n = sum(steps.values())
    return sum(w * dn for w, dn in steps.items()) / n if n > 0 else None
