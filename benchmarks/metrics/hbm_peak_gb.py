"""The chip's memory at its fullest, GB: the largest over the cell's chips
of ``peak_bytes_in_use + peak_bytes_reserved`` from the program's
``counters()["memory"]`` (the runtime's ``memory_stats()``), the sum
``run.py`` prints as ``device.memory_peak_bytes``: live arrays are "in use"
and a running program's temporaries "reserved", and the chip holds both.
The parts are printed beside it."""

from ddbench import passes


def read(ctx):
    memory = passes.counter(ctx, "memory")
    if not memory:
        return None
    peaks = {dev: m["peak_bytes_in_use"] + m.get("peak_bytes_reserved", 0)
             for dev, m in memory.items() if "peak_bytes_in_use" in m}
    if not peaks:
        return None
    dev = max(peaks, key=peaks.get)
    print(f"hbm_peak_gb: {dev}: "
          + ", ".join(f"{k} {v}" for k, v in memory[dev].items()),
          flush=True)
    return peaks[dev] / 1e9
