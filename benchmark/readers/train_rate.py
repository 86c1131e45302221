"""Tokens of the steps completed inside the window / window / chips.  The
window ends on ``block_until_ready`` of the last step's loss."""


def read(obs):
    if obs.get("kind") != "train" or not obs["steps"]:
        return None
    t0, t1 = obs["window"]
    return obs["steps"] * obs["tokens_per_step"] / (t1 - t0) / obs["chips"]
