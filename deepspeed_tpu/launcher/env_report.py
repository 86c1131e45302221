"""`dstpu_report` — environment/compat report (reference: bin/ds_report ->
deepspeed/env_report.py)."""
from __future__ import annotations

import sys


def collect() -> dict:
    info: dict = {}
    try:
        import jax

        info["jax"] = jax.__version__
        info["platform"] = jax.devices()[0].platform
        info["device_kind"] = jax.devices()[0].device_kind
        info["device_count"] = jax.device_count()
        info["process_count"] = jax.process_count()
    except Exception as e:
        info["jax_error"] = str(e)
    for mod in ("flax", "optax", "orbax.checkpoint", "einops", "numpy"):
        try:
            m = __import__(mod)
            info[mod] = getattr(m, "__version__", "present")
        except ImportError:
            info[mod] = "MISSING"
    if "platform" in info:
        info["pallas"] = (
            "Mosaic kernels" if info["platform"] == "tpu"
            else "jnp bodies (kernels run only under an explicit "
                 "set_interpret(True))"
        )
    try:
        from ..ops.op_builder import op_report

        for name, st in op_report().items():
            info[f"op/{name}"] = (
                ("compatible" if st["compatible"] else "INCOMPATIBLE")
                + (", built" if st["built"] else "")
            )
    except Exception as e:
        info["native_ops"] = f"error: {e}"
    import deepspeed_tpu

    info["deepspeed_tpu"] = deepspeed_tpu.__version__
    return info


def main() -> int:
    info = collect()
    width = max(len(k) for k in info)
    print("-" * 50)
    print("deepspeed_tpu environment report")
    print("-" * 50)
    for k, v in info.items():
        print(f"{k:<{width}}  {v}")
    print("-" * 50)
    return 0


if __name__ == "__main__":
    sys.exit(main())
