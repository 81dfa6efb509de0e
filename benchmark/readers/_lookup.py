"""Arguments of a metric file that point into the run: "facts.seq",
"config.hidden_size", "workload.batch"; anything else is taken as it is."""


def resolve(value, run):
    if isinstance(value, str) and "." in value:
        where, key = value.split(".", 1)
        if where in ("facts", "config", "workload"):
            return run[where][key]
    return value
