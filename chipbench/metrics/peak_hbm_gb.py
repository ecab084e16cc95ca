"""Peak device memory of the run up to the window's close, on the
fullest chip: memory_stats()["peak_bytes_in_use"] plus
["peak_bytes_reserved"], the region where a TPU keeps a program's
temporaries (GB, 1e9 bytes; chipbench/common.device_report)."""


def read(rec: dict):
    peak = rec["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
