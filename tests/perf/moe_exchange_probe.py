"""What a layer of OLMoE's experts costs on the wire of a four-chip host, both ways:

    chiprun --chips 4 -- python tests/perf/moe_exchange_probe.py

``gather``: what ``parallel/moe.DroplessMoE`` does today. Every chip all-gathers the bf16
weights of the 48 experts it does not own (gate|up and down), forward and again backward,
and reduce-scatters their gradients: traffic that grows with the parameters.
``tokens``: what the issue named and the layer does not do. At an EVEN router every chip
sends three quarters of its 8,192 x 8 rows of 2,048 bf16 to the owners of their experts and
takes the results back, forward and again backward: four exchanges a layer, traffic that
grows with the tokens. ``all_to_all`` is the even split as one dense collective;
``ragged_all_to_all`` is the same rows into a receive buffer of the worst case (4 x), the
only static bound that never drops. Nothing here computes an expert: wall milliseconds a
call around ``block_until_ready``, median of ``--repeats``, on stdout and in
``chiprun_out/moe_exchange_probe.json``. ``--compile-only`` lowers for a described v5e:2x2.
"""

import argparse
import json
import os
import statistics
import time

E, H, F, K, N = 64, 2048, 1024, 8, 8192          # experts, widths, top-k, tokens a chip


def programs(mesh, ep):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    rows = N * K                                   # a chip's assignments
    even = rows // ep

    def on_chips(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                                     check_vma=False))

    def gather(gu, d):                             # [E/ep, H, 2F], [E/ep, F, H] a chip
        return jax.lax.all_gather(gu, "data", tiled=True), jax.lax.all_gather(d, "data", tiled=True)

    def scatter(gu, d):                            # the gradients of all E, summed to the owners
        return (jax.lax.psum_scatter(gu, "data", tiled=True),
                jax.lax.psum_scatter(d, "data", tiled=True))

    def dense(x):                                  # [rows, H] a chip, sorted by owner, even
        return jax.lax.all_to_all(x.reshape(ep, even, H), "data", 0, 0).reshape(rows, H)

    def ragged(x):
        me = jax.lax.axis_index("data")
        sizes = jnp.full((ep,), even, jnp.int32)
        out = jnp.zeros((ep * rows, H), x.dtype)   # the worst case: every row of every chip
        return jax.lax.ragged_all_to_all(
            x, out, jnp.arange(ep, dtype=jnp.int32) * even, sizes,
            jnp.full((ep,), me * even, jnp.int32), sizes, axis_name="data")[:rows]

    bf16 = jnp.bfloat16
    shapes = {"gu": ((E, H, 2 * F), bf16), "d": ((E, F, H), bf16),
              "gu_all": ((ep * E, H, 2 * F), bf16), "d_all": ((ep * E, F, H), bf16),
              "x": ((ep * rows, H), bf16)}
    data = P("data")
    return shapes, {
        "gather.all_gather": (on_chips(gather, (data, data), (data, data)), ("gu", "d")),
        "gather.reduce_scatter": (on_chips(scatter, (data, data), (data, data)), ("gu_all", "d_all")),
        "tokens.all_to_all": (on_chips(dense, (data,), data), ("x",)),
        "tokens.ragged_all_to_all": (on_chips(ragged, (data,), data), ("x",)),
    }


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--repeats", type=int, default=20)
    parser.add_argument("--compile-only", action="store_true")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if args.compile_only:
        from jax.experimental import topologies
        devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    else:
        devices = jax.devices()
    ep = 4
    mesh = Mesh(np.asarray(devices[:ep]), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    shapes, progs = programs(mesh, ep)
    out = {"device": devices[0].device_kind, "chips": ep, "ms": {}}
    for name, (fn, operands) in progs.items():
        try:
            if args.compile_only:
                fn.lower(*(jax.ShapeDtypeStruct(*shapes[o], sharding=sharding)
                           for o in operands)).compile()
                out["ms"][name] = "compiles"
                continue
            made = [jax.jit(lambda s=shapes[o]: jnp.ones(*s), out_shardings=sharding)()
                    for o in operands]
            jax.block_until_ready(fn(*made))
            times = []
            for _ in range(args.repeats):
                t = time.perf_counter()
                jax.block_until_ready(fn(*made))
                times.append((time.perf_counter() - t) * 1e3)
            out["ms"][name] = statistics.median(times)
            del made
        except Exception as e:      # one collective the compiler refuses must not cost the others
            out["ms"][name] = f"failed: {type(e).__name__}: {str(e)[:300]}"
    ms = out["ms"]
    if all(isinstance(v, float) for v in ms.values()):
        # a layer of a step: forward and backward gather + one reduce-scatter; four exchanges
        out["layer_ms"] = {"gather": 2 * ms["gather.all_gather"] + ms["gather.reduce_scatter"],
                           "tokens.all_to_all": 4 * ms["tokens.all_to_all"],
                           "tokens.ragged_all_to_all": 4 * ms["tokens.ragged_all_to_all"]}
    print(json.dumps(out))
    if not args.compile_only:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/moe_exchange_probe.json", "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    main()
