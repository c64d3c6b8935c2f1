"""Share of the flash kernels' device time in the traced window that their
least time at the chip's peaks fills: max(FLOPs / bf16 peak, bytes / HBM
peak) over the summed device time of the ``flash_fwd``, ``flash_bwd_dkv``
and ``flash_bwd_dq`` kernels of the step. The configuration's module counts
FLOPs and bytes per call from the shapes (``flash_counts``) and reads the
trace in its ``check``. None where no flash kernel ran."""


def read(run):
    return run.layer.get("flash_attention_roofline")
