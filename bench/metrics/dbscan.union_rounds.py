"""Mean union-fixpoint rounds per step of the window, as the program
counts them (DbscanResult.num_rounds, HaloPipelineResult.rounds)."""


def read(run):
    rounds = [int(s["counters"]["union_rounds"]) for s in run["steps"]
              if "union_rounds" in s["counters"]]
    return sum(rounds) / len(rounds) if rounds else None
