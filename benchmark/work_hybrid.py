"""Work functions of the hybrid configuration's layers: the operations
and bytes a step NEEDS, from the published sizes and from what the
program's ``unified_step`` span says the step held (rows, experts hit,
assignments on the held experts).  Only work certainly done is counted,
so a share of a roofline computed from these cannot pass 100 %: an
expert's weights once if at least one live token chose it (not the
experts the dense mix also multiplies by zero), an assignment's two
matmuls once, a live row's state read once and written once."""
from __future__ import annotations


def expert_bytes(model: dict) -> float:
    """One routed expert's two matrices in bf16."""
    lat = model.get("moe_latent_size") or model["hidden_size"]
    return 2.0 * lat * model["moe_intermediate_size"] * 2


def moe_routed_work(model: dict, attrs: dict):
    """(FLOPs, bytes) of the routed experts over ALL expert layers in one
    step: ``moe_experts_hit`` (held experts with >= 1 live token, summed
    over the expert layers) x one expert's weights read once; ``moe_local``
    live assignments x two matmuls of ``latent x width`` at 2 FLOPs a
    multiply-add."""
    lat = model.get("moe_latent_size") or model["hidden_size"]
    flops = float(attrs.get("moe_local", 0)) * 4.0 * lat * \
        model["moe_intermediate_size"]
    return flops, float(attrs.get("moe_experts_hit", 0)) * expert_bytes(model)


def state_slot_bytes(model: dict) -> float:
    """One sequence's recurrent state in ONE mamba2 layer: the float32
    scan state [heads, head_dim, state] and the bf16 conv tail [K - 1,
    inner + 2 * groups * state]."""
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    conv = inner + 2 * model["n_groups"] * model["ssm_state_size"]
    return inner * model["ssm_state_size"] * 4.0 + \
        (model["conv_kernel"] - 1) * conv * 2.0


def ssm_state_work(model: dict, attrs: dict):
    """(FLOPs, bytes): every live row's state read and written once in
    each mamba2 layer.  The recurrence's arithmetic (a few operations a
    state element) is left out: the bytes bound it."""
    layers = model["hybrid_override_pattern"].count("M")
    return 0.0, float(attrs.get("rows", 0)) * layers * 2.0 * \
        state_slot_bytes(model)


WORK_FNS = {"moe_routed_work": moe_routed_work,
            "ssm_state_work": ssm_state_work}
