"""Device seconds of the search programs in a reduced trace."""

#: executables of the jitted search programs, as the trace names them
PROGRAMS = ("jit__rb_descend", "jit__sa_sweeps", "jit__bf_chunk",
            "jit__bf_chunk_shard")


def device_s(trace):
    if trace is None:
        return None
    s = sum(v for k, v in trace["modules"].items() if k in PROGRAMS)
    return s if s > 0 else None
