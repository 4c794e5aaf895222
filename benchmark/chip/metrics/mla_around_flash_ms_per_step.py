"""Milliseconds of a device step under the scope ``mla`` (the latent
attention mixer, forward and backward of every such layer) that are not the
flash kernels: the device time traced under that scope less the time
traced under ``mx_flash_*`` inside it (the kernels
``mla_flash_roofline_by_arch`` reads), a step of the traced window.  It is
what stands around the kernels: the projections and their norms, the
rotation, the key materialised over the heads, transposes.  A program that
opens no such scope has nothing to read."""
import mixer_reduce
import trace_reduce

KERNELS = r"(^|[/(])mla[/)](.*/)?mx_flash_[^/]*/"


def read(run):
    whole, steps = (mixer_reduce.scope_seconds(run, "mla"),
                    mixer_reduce.steps(run))
    if whole is None or not steps:
        return None
    kernels, _ = trace_reduce.scope_seconds(run["trace"], KERNELS,
                                            *run["trace_window"])
    return 1e3 * (whole - kernels) / steps
