"""The whole PPO cycle's share of the chip's peak: lib/counts.py FLOPs of one cycle over the mean cycle time."""


def read(ctx):
    m, mix, peaks = ctx["measured"], ctx["mix"], ctx["peaks"]
    if not peaks or not m.get("rollout_s"):
        return None
    flops = ctx["counts"].ppo_cycle_flops(
        ctx["spec"], mix["batch"], mix["prompt_tokens"], mix["gen_tokens"],
        ctx["cell"]["model"]["num_layers_unfrozen"], mix["method"]["ppo_epochs"])
    cycle_s = (sum(m["rollout_s"]) + sum(m["update_s"])) / len(m["rollout_s"])
    ctx["notes"]["cycle_flops"] = flops
    return 100.0 * flops["total"] / cycle_s / (peaks["flops_bf16"] * ctx["device"]["count"])
