"""Traffic kinds: one module each, found by the ``kind`` a traffic file names.

A kind exposes ``build(traffic, *, seed, seconds, vocab) -> plan``.  Serving
plans offer ``ramp_s``, ``initial()`` and ``on_finish(request, t, generated)``
with times in seconds relative to the window's start (the ramp is negative);
training plans offer ``batches(rows)``.  A new kind is a new module here, not
a branch in an old one.
"""


class Request:
    """What a serving plan hands the driver: whose turn it is and what to send."""

    __slots__ = ("session", "turn", "prompt", "max_new")

    def __init__(self, session: int, turn: int, prompt: list, max_new: int):
        self.session, self.turn, self.prompt, self.max_new = session, turn, prompt, max_new
