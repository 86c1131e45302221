"""The held experts' share (%) of their roofline in a TRAINING step: the
least time the traced steps NEED of the expert layers - forward and backward
of the (token, expert) pairs the program COUNTED on its held experts
(``counters``: ``expert_pairs_held``, all layers; ``costs_experts_train.py``) -
over the device time of the ops under ``scope`` in every form it takes in the
step (forward, recomputation, backward: one class of ``classes``, a file of
``benchmark/scopes``).  Recomputation is time spent and not work needed.
Steps in the trace come from the run's own rate, as in ``flash_roofline``."""
from .. import costs, costs_experts_train, harness, xprograms
from ..peaks import peaks_for


def read(obs, classes, scope):
    c = obs.get("counters") or {}
    progs = xprograms.of(obs)
    if progs is None or obs["device"]["platform"] != "tpu" or not obs.get("steps") \
            or not c.get("expert_pairs_held"):
        return None
    from deepspeed_tpu import telemetry

    if "_scopes" not in obs:
        obs["_scopes"] = telemetry.program_scopes()
    spec = harness.load_json(harness.HERE / "scopes" / f"{classes}.json")
    spent = xprograms.class_seconds(progs, obs["_scopes"], spec["classes"],
                                    spec["default"]).get(scope, 0.0)
    if spent <= 0:
        return None
    m, peaks = obs["model"], peaks_for(obs["device"]["kind"])
    layers = m["num_hidden_layers"]
    pairs = c["expert_pairs_held"] / obs["steps"] / layers  # a layer and step
    shape = (pairs, m["num_experts"], m["hidden_size"], m["moe_intermediate_size"])
    a_step = layers * (
        costs.roofline_min_s(*costs_experts_train.experts_fwd(*shape), peaks)
        + costs.roofline_min_s(*costs_experts_train.experts_bwd(*shape), peaks))
    t0, t1 = obs["window"]
    steps = obs["steps"] / (t1 - t0) * obs["trace"].window_s
    return 100.0 * steps * a_step / spent
