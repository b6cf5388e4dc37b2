"""peak_hbm_gib: the device allocator's peak_bytes_in_use on the fullest
chip, read when the window closes and before the reference runs."""


def read(rec):
    peak = rec.get("memory_peak_bytes")
    return None if peak is None else peak / 2.0 ** 30
