"""How uneven the routers' choice was while the program's own rule moved
the selection biases: the busiest of ALL the published experts over the
mean expert, in the step's worst sparse layer
(`Trainer.last_counters["expert_loads"]`, counted where the router
chooses, held here or not), the mean over the window's steps. Set-up
starts the biases balanced; 1 would be even, and a rule that lost the
balance would read higher run after run."""


def read(ctx):
    steps = ctx["counters"].get("load_max_over_mean")
    if not steps:
        return None
    return sum(steps) / len(steps)
