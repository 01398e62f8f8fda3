"""Device time and roofline share of the operations that a kernel's op
kinds own, for the per-kernel readers under `metrics/`. The owner of a
device operation is `program_trace`'s: the `mx.<op>.<node>` scope of the
graph node that made it. Nothing to read (no trace, no program table, no
such op in the traced steps) reads as None."""
import harness
import program_trace

KINDS = {
    "linear_attention": ("_contrib_gated_delta_rule", "_contrib_causal_conv1d",
                         "_contrib_gated_rms_norm"),
    "attention": ("_contrib_causal_gqa_attention",
                  "_contrib_rotary_embedding"),
    "moe": ("_contrib_moe_held_ffn", "_contrib_shared_expert_ffn"),
}


def device_ms(run, kernel):
    dev = program_trace.analyse(run)["device"]
    traced = run.get("traced_steps")
    if dev is None or not traced:
        return None
    secs = [s for s, _m, _f, _c, kind in dev["joined"]
            if kind in KINDS[kernel]]
    return 1e3 * sum(secs) / traced if secs else None


def roofline_pct(run, kernel):
    ms = device_ms(run, kernel)
    if not ms:
        return None
    flops = harness.load_file("flops", run["config"]["flops"])
    if not hasattr(flops, "kernel_counts"):
        return None
    ops, least_bytes = flops.kernel_counts(run["config"],
                                           run["batch"])[kernel]
    peak = run["peak"]
    least = max(ops / (peak["bf16_flops_per_s"] * run["chips"]),
                least_bytes / (peak["hbm_bytes_per_s"] * run["chips"]))
    return 100.0 * least / (ms / 1e3)
