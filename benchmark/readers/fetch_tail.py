"""How long the host went without tokens that existed (ms): over the chain of
every execution a ``tick_collect`` fetched inside the capture
(``xruntime.chain``: enqueue and execution tied by ``run_id``, collect and
dispatch span by the collect's ``of``), percentile ``q`` (100: the capture's
worst) of the time from the execution's END to one instant of its fetch.

``link`` ``all``: ``returned - max(end, the collect's start)`` - the whole
tail, counted from where the thread began to wait if that is later; or one
link of it, counted the same way: ``notice`` (the program ended -> the runtime
learned it, ``tpu::System::Execute=>Done``), ``transfer`` (-> the result's
copy landed, ``TransferFromDevice=>IssueEvent=>Done``), ``wake`` (->
``np.asarray`` returned to the thread).  The three add up to ``all``; a fetch
that waits (S12) is late in ONE of them.

None where there is no chain (no capture, a capture without the runtime's
events, a program whose collects name no dispatch, spans dropped) or under 3
executions chained."""
from .. import xruntime
from ..stats import percentile


def read(obs, link, q):
    found = xruntime.chain(obs)
    if found is None or len(found.links) < 3:
        return None
    return percentile([1e3 * xruntime.LINKS[link](l) for l in found.links], q)
