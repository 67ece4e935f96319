"""Share of the v5e HBM roofline reached by the Reed-Solomon coding
kernel (``kernels/rs_bitmatmul.py``) while encoding, in %.

Useful bytes (K data rows read and P parity rows written per group, at
the unpadded chunk length; ``bench/work.py``) over the HBM peak, divided
by the summed device time of the kernel's events in the traced window.
Nothing to read where the window ran no coding kernel.
"""

import trace_reduce


def read(obs):
    kernel_s = trace_reduce.kernel_seconds(obs["trace"], trace_reduce.CODING_KERNEL)
    useful = obs["work"].get("encode_bytes", 0)
    if kernel_s <= 0 or useful <= 0:
        return None
    return 100.0 * useful / obs["peaks"]["hbm_bytes_per_s"] / kernel_s
