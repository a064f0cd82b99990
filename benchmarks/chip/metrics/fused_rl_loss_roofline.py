"""Share of its roofline the fused RL-loss kernel reached in the traced
window: for every forward and backward call, the least time the chip
could take, max(bytes / peak bandwidth, operations / peak FLOP/s) with
bytes and operations from the call's logits shape (``flops.py``), summed
and divided by the calls' summed device time. ``bound`` says which of
the two limits the least time."""
import flops

KERNELS = {"fwd": "fused_rl_loss_fwd_kernel", "bwd": "fused_rl_loss_bwd_kernel"}


def read(run):
    if run.trace is None:
        return None
    need = spent = by_bytes = by_ops = 0.0
    for name, seconds, text in run.trace["custom_calls"]:
        kind = next((k for k, pat in KERNELS.items() if pat in name), None)
        if kind is None:
            continue
        dtype, n, v = flops.call_shape(text)
        ops, nbytes = flops.fused_rl_loss_cost(kind, n, v, dtype)
        t_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
        t_ops = ops / run.peaks["bf16_flops_per_s"]
        need += max(t_bytes, t_ops)
        by_bytes += t_bytes
        by_ops += t_ops
        spent += seconds
    if spent <= 0:
        return None
    return {"value": 100.0 * need / spent,
            "bound": "memory" if by_bytes >= by_ops else "compute"}
