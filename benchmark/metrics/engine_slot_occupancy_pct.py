"""Share of decode-step rows that produced a delivered token: the
window's decoded tokens (engine ``tokens_total`` less the first tokens,
which prefills emit) over ``steps_total`` x slots."""


def read(out):
    s = out.get("serve")
    if not s or not s["steps"]:
        return None
    first = sum(1 for r in s["requests"] if r.t_first is not None)
    return 100.0 * (s["tokens_engine"] - first) / (s["steps"] * s["slots"])
