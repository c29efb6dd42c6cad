"""Reader ``memory_stats``: a key of ``device.memory_stats()`` on the
fullest chip, in GB (1e9 bytes).  args: key."""
import jax


def read(args, facts):
    vals = [(d.memory_stats() or {}).get(args["key"])
            for d in jax.devices()[:facts["chips"]]]
    vals = [v for v in vals if v is not None]
    return max(vals) / 1e9 if vals else None
