"""Milliseconds of a device step under the scope ``moe_experts`` (the
experts' operator, ``ops/moe.py:grouped_ffn``, forward and backward of every
expert layer) that are not the grouped products: the device time traced
under that scope less the time traced under ``mx_gmm*`` inside it (the
kernels ``moe_gmm_roofline`` reads), a step of the traced window.  It is
what stands around the products: rows gathered into the sorted layout, the
activation, the routing weight, the sum over a token's assignments, the
layout's integers.  A program that opens no such scope has nothing to read;
one whose grouped products are not those kernels (any before PR 31) reads
the whole of the scope."""
import mixer_reduce
import trace_reduce

PRODUCTS = r"(^|[/(])moe_experts[/)](.*/)?mx_gmm[^/]*/"


def read(run):
    whole, steps = (mixer_reduce.scope_seconds(run, "moe_experts"),
                    mixer_reduce.steps(run))
    if whole is None or not steps:
        return None
    products, _ = trace_reduce.scope_seconds(run["trace"], PRODUCTS,
                                             *run["trace_window"])
    return 1e3 * (whole - products) / steps
