"""The histogram kernel's share of its roofline: least time for the rows
that had to be histogrammed (``work``: root + smaller child of every
split, (F + 12) bytes a row) over the device time of the kernel's events
in the trace."""
import re

import work

# the Pallas histogram kernel's custom call, as the device trace names it:
# "%_hist_pallas_impl.21 = f32[16,18432]{...} custom-call(s32[72,2000896]..."
KERNEL = re.compile(r"^%_hist_pallas\w*(\.\d+)? = .*custom-call\(")


def read(ctx):
    trace = ctx["trace"]
    if not trace or not ctx["peaks"]:
        return None
    kernel_s = sum(s for n, s in trace["op_s"].items() if KERNEL.search(n))
    if kernel_s <= 0:
        return None
    least = work.least_seconds(ctx["work"]["parts"]["histogram"],
                               ctx["peaks"])
    return 100.0 * least / kernel_s
