"""Reader ``driver_value``: a number the driver itself took with its own
timestamps (e.g. a tail recorded but never judged).  args: key."""


def read(args, facts):
    return facts["values"].get(args["key"])
