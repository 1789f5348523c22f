"""Device kernels: the least time to read every payload byte shipped to the
device in the window once at the chip's HBM peak, over the device's busy
time in the window.  Chunking and fingerprinting are all the device work of
these cells, and each must read every payload byte at least once, so the
share cannot pass 100%."""


def read(rec):
    t, peaks = rec["trace"], rec["peaks"]
    payload = rec["sched"]["payload_bytes"]
    if not t or not peaks or t["busy_s"] <= 0 or payload <= 0:
        return None
    return 100.0 * payload / float(peaks["hbm_bytes_per_s"]) / t["busy_s"]


CASE = ({}, 100.0 * 819_000 / 819e9 / 0.25)
