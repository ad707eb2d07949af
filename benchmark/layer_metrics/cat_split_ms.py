"""Device ms a round under ``xtpu.cat_split``: the categorical arm of the
split search (``tree/grow.py``), self time of the round programs' ops whose
scope path holds that stage (it wraps an ``xtpu.eval`` of its own). None
where no trace was taken or no op carries the stage (a program without
it)."""


def read(facts):
    from lib import program_trace as pt
    trace = facts.get('trace')
    if not trace or not trace.get('rounds'):
        return None
    planes = pt.last_trace()
    if planes is None:
        return None
    total, stack, seen = 0.0, [], False

    def pop():
        nonlocal total
        mine, start, end, inner = stack.pop()
        if mine:
            total += max(0.0, (end - start) - inner) / 1e9

    for start, end, inside, tf_op in pt.round_ops(
            planes, facts['platform'], facts['round_programs']):
        if end <= start:
            continue
        while stack and stack[-1][2] <= start:
            pop()
        if stack:
            end = min(end, stack[-1][2])
            stack[-1][3] += end - start
        mine = inside and 'xtpu.cat_split' in (tf_op or '')
        seen = seen or mine
        stack.append([mine, start, end, 0.0])
    while stack:
        pop()
    return 1e3 * total / trace['rounds'] if seen else None
