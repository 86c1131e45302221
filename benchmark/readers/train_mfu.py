"""Model FLOP/s utilization (%): tokens/s x the benchmark's own FLOPs a token
requires (forward + backward, recomputation not counted) over chips x peak.
A utilization of the model, not a kernel's roofline share."""
from .. import costs
from ..peaks import peaks_for
from . import train_rate


def read(obs):
    rate = train_rate.read(obs)
    if rate is None or obs["device"]["platform"] != "tpu":
        return None
    per_token = costs.train_flops_per_token(obs["model"], obs["seq"])
    return 100.0 * rate * per_token / peaks_for(obs["device"]["kind"])["bf16_flops_per_s"]
