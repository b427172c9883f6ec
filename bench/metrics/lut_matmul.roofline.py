"""Share of its roofline that the banked LUT-matmul kernel reaches
(layer "kernels"): the least time the chip could take for every LUT
matmul launch of the window, each ``max(operations / int8 peak, bytes /
HBM bandwidth)`` (``bench/count.py``: the work a LUT matmul needs, not
the one-hot MXU work that implements it), over the device time of the
kernel's events in the trace.  The kernel's events are found by name;
the XLA gathers that build its tables are outside this time."""

#: the banked Pallas kernel of ``repro/kernels/approx_matmul.py`` as it
#: appears in the trace: a TPU custom call named after its jitted wrapper
KERNEL = "approx_matmul_lut_bank_pallas"
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None or not run.lut_calls:
        return None
    kernel = run.trace.kernel_s(KERNEL, CUSTOM_CALL)
    if kernel <= 0:
        return None
    p = run.peaks
    least = sum(max(ops / p["int8_ops_per_s"], nbytes / p["hbm_bytes_per_s"])
                for ops, nbytes in run.lut_calls)
    return 100.0 * least / kernel
