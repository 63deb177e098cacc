"""Test helper: the stage program `execute_plan_spmd` compiles for a plan,
and the inputs it is called with — for tests that read the traced or the
lowered program."""

from auron_tpu.parallel import stage as S


def spied_program(plan, ctx, mesh, sources):
    """(the jitted stage program's function, its inputs) of one plan,
    compiled apart from whatever the process cached before."""
    saved = dict(S._PROGRAM_CACHE)
    S._PROGRAM_CACHE.clear()
    calls = []
    try:
        S.execute_plan_spmd(plan, ctx, mesh, sources)
        [(key, (shard, *boxes))] = S._PROGRAM_CACHE.items()
        S._PROGRAM_CACHE[key] = (
            lambda inputs: calls.append(inputs) or shard(inputs), *boxes)
        S.execute_plan_spmd(plan, ctx, mesh, sources)
    finally:
        S._PROGRAM_CACHE.clear()
        S._PROGRAM_CACHE.update(saved)
    return shard.__wrapped__, calls[0]
