"""Peak HBM in GiB on the fullest device, from the program's own
/debug/resources (deviceResidency.deviceMemory[].peakBytesInUse) after
the window. The program fills it only while a stack is resident."""


def read(params: dict, ctx: dict):
    res = ctx["scrapes"]["window_end"]["resources"]["subsystems"].get("deviceResidency", {})
    peaks = [d.get("peakBytesInUse") for d in res.get("deviceMemory", [])]
    peaks = [p for p in peaks if p]
    return max(peaks) / 2**30 if peaks else None
