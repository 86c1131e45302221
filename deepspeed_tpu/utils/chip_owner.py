"""One process for each chip.

A TPU chip belongs to one process at a time: a parent that has touched JAX
holds every chip of the host, and a child that needs one then fails or hangs.
Until the worker pool and the elastic agent are reworked around that (one
process driving N one-chip replicas), the places that spawn JAX children
refuse to start one that is not pinned to the CPU — with an error that says
so, instead of a hang.
"""
from __future__ import annotations

from typing import Mapping, Optional


def refuse_chip_children(env: Mapping[str, str], who: str,
                         platform: Optional[str] = None) -> None:
    """Raise unless a child started with ``env`` (and, where the child pins
    one itself, ``platform``) runs JAX on the CPU."""
    effective = platform or env.get("JAX_PLATFORMS", "")
    if effective.strip().lower() != "cpu":
        raise RuntimeError(
            f"{who} would start a child process on the default JAX backend "
            f"(platform {effective or 'unset'!r}). On a TPU host that child "
            "claims every chip, which the parent — or a sibling — already "
            "holds, and it hangs or fails: a chip belongs to one process. "
            "Pin the children to the CPU (JAX_PLATFORMS=cpu / "
            "spec['platform']='cpu'), or drive the chips from ONE process "
            "(InferenceEngineV2 on a mesh, or one engine per device)."
        )
