"""Device: peak bytes in use on the chip after the window
(``device.memory_stats()``), in GB."""


def read(facts):
    return facts["device"]["memory_peak_bytes"] / 1e9
