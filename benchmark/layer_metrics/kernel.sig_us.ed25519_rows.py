"""Device time of the Ed25519 fixed-comb rows program per real
signature, over the traced blocks.  The program's name on the trace's
'XLA Modules' line is kept here (bccsp/jaxtpu.py `_get_fn`:
jax.jit(ed25519.verify_words_rows); seen by hand in a v5e trace, PR 32):
`programs.json` is not this PR's to edit.  Fails, rather than guess,
where the trace's executions are not the lane's dispatches."""
from harness import BenchFailure, prom_delta

LANE = "ed25519-rows"
PROGRAM = "jit_verify_words_rows"


def read(obs):
    trace = obs.get("trace")
    before, after = obs.get("traced_prom_before"), obs.get("traced_prom_after")
    if not trace or before is None or after is None:
        return None
    prog = trace["programs"].get(PROGRAM)
    dispatches = prom_delta(before, after, "provider_lane_fill_count",
                            lane=LANE)
    if prog is None and dispatches == 0:
        return None
    executions = prog["executions"] if prog else 0
    # one execution of slack at each edge where the driver says so, as
    # for the P-256 lanes (readers.kernel_sig_us)
    if abs(executions - dispatches) > obs.get("trace_edge_slack", 0):
        raise BenchFailure(
            f"cannot tell the {LANE} lane's program in the trace: "
            f"{executions} executions named {PROGRAM!r}, {dispatches:.0f} "
            f"dispatches on the lane in the traced window")
    sigs = (prom_delta(before, after, "provider_lane_slots_total", lane=LANE)
            - prom_delta(before, after, "provider_pad_slots_total", lane=LANE))
    if sigs <= 0:
        return None
    return 1e6 * prog["device_s"] / sigs
