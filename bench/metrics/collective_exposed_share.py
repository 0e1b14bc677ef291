"""Share of the traced window in which a collective runs on a device
(all-gather, all-to-all, all-reduce) and no other operation does,
averaged over the cell's devices (profiler trace): the time the mesh
waits on its exchanges."""
from bench import trace

# a collective's event carries its opcode in its HLO text, whatever JAX
# named it: "%psum.95 = s32[8]{0} all-reduce(...)", "%all_to_all.210 =
# pred[4,1,96]{2,1,0} all-to-all(...)", "%all-gather.70 = ... all-gather(...)".
# A traced v5e 2x2 run of the mesh path (BDD at scale 1, 8 queries on 4
# shards, 48 cohorts) matched them with this pattern.
COLLECTIVE = r" (all-gather|all-to-all|all-reduce|collective-permute|reduce-scatter)(-start|-done)?\("


def value(ctx):
    red = ctx["trace"]
    if not red or not red["window_s"]:
        return None
    exposed = trace.exposed_seconds(red, COLLECTIVE)
    return None if exposed is None else 100.0 * exposed / red["window_s"]
