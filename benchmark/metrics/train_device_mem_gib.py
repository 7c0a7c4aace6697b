"""Peak bytes on the fullest chip: the larger of the allocator's peak and
the compiled step's own peak (`memory_analysis().peak_memory_in_bytes`),
since `peak_bytes_in_use` misses the step's temporaries on this runtime."""


def read(obs):
    peak = obs.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
