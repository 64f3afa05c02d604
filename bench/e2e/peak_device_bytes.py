"""``memory_stats()["peak_bytes_in_use"]`` after the window, the largest
over the cell's chips."""


def read(w):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in w.devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
