"""Seconds in the planner: the program's spans of category ``compile``
named ``ilp-solve*`` and ``stage-dp``."""


def read(obs):
    spans = [s for s in obs["program_spans"] if s["category"] == "compile"
             and (s["name"].startswith("ilp-solve") or
                  s["name"].startswith("stage-dp"))]
    if not spans:
        return None
    return sum(s["dur_us"] for s in spans) / 1e6
