"""entry + plan: WHERE conjuncts that name columns of one join input alone
and were still left in a filter ABOVE that join, per planned query: the
tracer's ``plan.pushdown`` counter (``above_joins``; written once per
planned query by ``plan/overrides.py:plan_query`` beside ``pushed``, the
conjuncts it moved below a join). A count. 0 where every such predicate
runs before the join pays for the rows it drops (a traffic file's
``require_at_most`` holds a traced run to that). Part B of a traced run.
Nothing where the program writes no such counter."""


def read(run):
    spans = run.get("spans")
    if not spans or not spans["queries"]:
        return None
    left = [e["args"]["above_joins"] for e in spans["events"]
            if e.get("ph") == "C" and e["name"] == "plan.pushdown"]
    return sum(left) / len(left) if left else None
