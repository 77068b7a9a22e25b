"""Wire bytes the destination received over the decoded (bfloat16)
parameter bytes of the model: a count, exact."""


def read(ctx, role="dest", message="layer fully received",
         field="total_bytes"):
    got = [int(r[field]) for r in ctx["logs_by_role"].get(role, ())
           if r.get("message") == message and field in r]
    if not got:
        return None
    return sum(got) / ctx["fabricate"].model_nbytes(ctx["config"])
