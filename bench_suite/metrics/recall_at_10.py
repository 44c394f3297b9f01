"""Share of the plain reference's exact top-10 ids among the served ids,
over every served row of the window."""


def read(run):
    if int(run.config["k"]) != 10:
        return None
    return run.check_values.get("recall")
