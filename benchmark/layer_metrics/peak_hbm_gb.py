"""Device memory the cell needs on its fullest chip, GB (1e9 bytes): the
``device`` key's ``memory_peak_bytes``. Not one reading: the larger of
``memory_stats()["peak_bytes_in_use"]``, which counts live arrays only,
and the live arrays after the window plus the temporaries the compiler
reports for the step program (``harness/device.py``). Shows how far the
cell fills the chip."""


def read(run):
    peak = run["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
