"""(counter ``num`` - counter ``num_less``) / (counter ``den`` - counter
``den_less``), times ``scale``: a ratio over what is left of two counters when
a part each also counts apart is taken out (the packs' share of counts that
exist for all dispatches and for the decode ticks).  None where a counter is
missing or the denominator is not positive."""


def read(obs, num, num_less, den, den_less, scale=1.0):
    c = obs.get("counters") or {}
    if any(k not in c for k in (num, num_less, den, den_less)):
        return None
    below = c[den] - c[den_less]
    return scale * (c[num] - c[num_less]) / below if below > 0 else None
