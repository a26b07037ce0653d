"""Game rounds per job, as the partitioner's ``stats`` count them."""


def read(ctx):
    jobs = ctx.results.get("jobs")
    if not jobs:
        return None
    rounds = [j["stats"].get("game_rounds") for j in jobs]
    if None in rounds:
        return None
    return sum(rounds) / len(rounds)
