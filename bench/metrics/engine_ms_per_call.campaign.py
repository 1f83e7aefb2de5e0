"""Engine time per call: the program's ``process.run`` span less its
``process.body`` (process, caching and store work around the body)."""


def read(run):
    per_call = []
    for spans in run.spans or []:
        total = sum(s["dur"] for s in spans if s["name"] == "process.run")
        body = sum(s["dur"] for s in spans if s["name"] == "process.body")
        if total:
            per_call.append(total - body)
    return sum(per_call) / len(per_call) * 1e3 if per_call else None
