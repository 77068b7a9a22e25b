"""Seconds of one ``plan_phases`` bucket of ``run_pod``'s summary."""


def read(ctx, phase):
    bucket = (ctx["round"].get("summary", {}).get("plan_phases", {})
              .get(phase))
    if not bucket:
        return None
    return float(bucket["ms"]) / 1000.0
