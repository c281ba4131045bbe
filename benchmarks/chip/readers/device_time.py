"""Device seconds per assembly in the modules a layer file lists."""


def read(trace, spec: dict, ctx: dict):
    seconds = trace.module_seconds(spec["modules"])
    if seconds <= 0 or ctx["assemblies"] == 0:
        return None
    return seconds / ctx["assemblies"]
