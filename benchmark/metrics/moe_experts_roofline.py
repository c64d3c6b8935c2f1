"""Share of the device time of the operations under the ``moe.experts``
scope in the traced window that the held experts' products fill at the
chip's peaks: FLOPs from the window's ``expert_assignments`` counts (three
products of 2·d·f, forward and backward), bytes from the held weights and
the routed rows (the configuration's ``expert_counts``). None where the
program has no such scope."""


def read(run):
    return run.layer.get("moe_experts_roofline")
