"""What a layer of OLMoE's experts costs on the wire of a four-chip host, both ways:

    chiprun --chips 4 -- python tests/perf/moe_exchange_probe.py

``gather``: what ``parallel/moe.DroplessMoE`` did until PR 27. Every chip all-gathers the bf16
weights of the 48 experts it does not own (gate|up and down), forward and again backward,
and reduce-scatters their gradients: traffic that grows with the parameters.
``direct``: what the layer does now (``parallel/moe.gather_pieces`` and its cotangent): the
same bytes as ``collective-permute``s, every chip's experts sent straight to each other chip
and every gradient straight to its owner. ``ring``: the same as neighbour hops, each piece
passed on by the chip that received it, one way round, and half of each chip's experts
each way round. ``+matmul`` puts a chain of dense products of about a layer's forward
beside the exchange in one program, so that (with ``matmul`` alone) the exchange's EXPOSED
time can be read; ``@0132`` is the same program with the chips in the order 0, 1, 3, 2 on
the axis, a ring of the 2 x 2 host's links (``parallel/mesh.build_mesh`` keeps ``jax.devices()``
order, 0, 1, 2, 3; alone on the wire only the two-way ring gained from the other, in the
cell the direct form did too, 1.6-1.8 %: PERF.md, PR 27).
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
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

E, H, F, K, N = 64, 2048, 1024, 8, 8192          # experts, widths, top-k, tokens a chip
M = 8192                                         # the dense product beside an exchange


def programs(mesh, ep):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel.moe import gather_pieces
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

    def hop(x, step):
        return jax.lax.ppermute(x, "data", [(i, (i + step) % ep) for i in range(ep)])

    def ring_two_way(w):                           # half of a chip's experts each way round
        half = w.shape[0] // 2
        pieces, up, down = [w], w[:half], w[half:]
        for _ in range(ep - 1):
            up, down = hop(up, 1), hop(down, -1)
            pieces += [up, down]
        return tuple(pieces)

    def scatter_two_way(grads):                    # ring_two_way's cotangent, as the layer's
        half = grads[0].shape[0] // 2

        def add(a, b):
            return (a.astype(jnp.float32) + b.astype(jnp.float32)).astype(b.dtype)
        up, down = grads[1], grads[2]
        for s in range(2, ep):
            up, down = add(hop(up, 1), grads[2 * s - 1]), add(hop(down, -1), grads[2 * s])
        return jnp.concatenate([add(hop(up, 1), grads[0][:half]),
                                add(hop(down, -1), grads[0][half:])])

    def ring_one_way(w):                           # each piece passed on by its receiver
        pieces = [w]
        for _ in range(ep - 1):
            pieces.append(hop(pieces[-1], 1))
        return tuple(pieces)

    def scatter_one_way(grads):                    # a float32 add and one rounding a hop
        total = grads[1]
        for s in range(2, ep + 1):
            total = (hop(total, 1).astype(jnp.float32)
                     + grads[s % ep].astype(jnp.float32)).astype(total.dtype)
        return total

    def direct(gu, d):
        return gather_pieces(gu, "data"), gather_pieces(d, "data")

    def direct_scatter(gu, d):                     # [ep, E/ep, ...] a chip: a gradient a piece
        return tuple(jax.vjp(lambda w: gather_pieces(w, "data"), g[0])[1](tuple(g))[0]
                     for g in (gu, d))

    def ring(gu, d):
        return ring_one_way(gu), ring_one_way(d)

    def ring_scatter(gu, d):
        return scatter_one_way(tuple(gu)), scatter_one_way(tuple(d))

    def ring2(gu, d):
        return ring_two_way(gu), ring_two_way(d)

    def ring2_scatter(gu, d):
        def pieces(g):
            half = g.shape[1] // 2
            return (g[0],) + tuple(g[s, :half] if up else g[s, half:]
                                   for s in range(1, ep) for up in (True, False))
        return scatter_two_way(pieces(gu)), scatter_two_way(pieces(d))

    def matmul(m):                                 # [M, M] a chip: 4 x 5.6 ms at peak
        y = m
        for _ in range(4):
            y = jnp.dot(y, m)
        return y

    def beside(exchange):
        return lambda m, *operands: (matmul(m), exchange(*operands))

    bf16 = jnp.bfloat16
    shapes = {"gu": ((E, H, 2 * F), bf16), "d": ((E, F, H), bf16),
              "gu_all": ((ep * E, H, 2 * F), bf16), "d_all": ((ep * E, F, H), bf16),
              "x": ((ep * rows, H), bf16), "m": ((ep * M, M), bf16)}
    data = P("data")
    per = E // ep
    pieces, pieces2 = (data,) * ep, (data,) * (2 * ep - 1)
    shapes["gu_grads"] = ((ep * ep, per, H, 2 * F), bf16)
    shapes["d_grads"] = ((ep * ep, per, F, H), bf16)
    return shapes, {
        "matmul": (on_chips(matmul, (data,), data), ("m",)),
        "ring.one_way": (on_chips(ring, (data, data), (pieces, pieces)), ("gu", "d")),
        "ring.one_way.scatter": (on_chips(ring_scatter, (data, data), (data, data)),
                                 ("gu_grads", "d_grads")),
        "ring.two_way": (on_chips(ring2, (data, data), (pieces2, pieces2)), ("gu", "d")),
        "ring.two_way.scatter": (on_chips(ring2_scatter, (data, data), (data, data)),
                                 ("gu_grads", "d_grads")),
        "direct": (on_chips(direct, (data, data), (pieces, pieces)), ("gu", "d")),
        "direct.scatter": (on_chips(direct_scatter, (data, data), (data, data)),
                           ("gu_grads", "d_grads")),
        "direct+matmul": (on_chips(beside(direct), (data, data, data),
                                   (data, (pieces, pieces))), ("m", "gu", "d")),
        "direct.scatter+matmul": (on_chips(beside(direct_scatter), (data, data, data),
                                           (data, (data, data))), ("m", "gu_grads", "d_grads")),
        "ring.one_way+matmul": (on_chips(beside(ring), (data, data, data),
                                         (data, (pieces, pieces))), ("m", "gu", "d")),
        "ring.one_way.scatter+matmul": (
            on_chips(beside(ring_scatter), (data, data, data), (data, (data, data))),
            ("m", "gu_grads", "d_grads")),
        "ring.two_way+matmul": (on_chips(beside(ring2), (data, data, data),
                                         (data, (pieces2, pieces2))), ("m", "gu", "d")),
        "ring.two_way.scatter+matmul": (
            on_chips(beside(ring2_scatter), (data, data, data), (data, (data, data))),
            ("m", "gu_grads", "d_grads")),
        "gather.all_gather+matmul": (on_chips(beside(gather), (data, data, data),
                                              (data, (data, data))), ("m", "gu", "d")),
        "gather.reduce_scatter+matmul": (on_chips(beside(scatter), (data, data, data),
                                                  (data, (data, data))), ("m", "gu_all", "d_all")),
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
    out = {"device": devices[0].device_kind, "chips": ep, "ms": {}}
    for order in ((0, 1, 2, 3), (0, 1, 3, 2)):
        mesh = Mesh(np.asarray([devices[i] for i in order]), ("data",))
        sharding = NamedSharding(mesh, P("data"))
        shapes, progs = programs(mesh, ep)
        for name, (fn, operands) in progs.items():
            if order != (0, 1, 2, 3):        # the rings again, the chips in another order
                if not name.startswith(("ring.", "direct", "matmul")):
                    continue
                name += "@" + "".join(map(str, order))
            try:
                if args.compile_only:
                    fn.lower(*(jax.ShapeDtypeStruct(*shapes[o], sharding=sharding)
                               for o in operands)).compile()
                    out["ms"][name] = "compiles"
                    continue
                made = [jax.jit(lambda s=shapes[o]: jnp.full(s[0], 1e-3, s[1]),
                                out_shardings=sharding)() for o in operands]
                jax.block_until_ready(fn(*made))
                times = []
                for _ in range(args.repeats):
                    t = time.perf_counter()
                    jax.block_until_ready(fn(*made))
                    times.append((time.perf_counter() - t) * 1e3)
                out["ms"][name] = statistics.median(times)
                del made
            except Exception as e:      # one program the compiler refuses must not cost the others
                out["ms"][name] = f"failed: {type(e).__name__}: {str(e)[:300]}"
    ms = out["ms"]
    if all(isinstance(v, float) for v in ms.values()):
        # a layer of a step: forward and backward gather + one reduce-scatter; four exchanges
        out["exposed_ms"] = {
            k.replace("+matmul", ""): ms[k] - ms["matmul" + "".join(k.partition("@")[1:])]
            for k in ms if "+matmul" in k}
        out["layer_ms"] = {"gather": 2 * ms["gather.all_gather"] + ms["gather.reduce_scatter"],
                           "direct": 2 * ms["direct"] + ms["direct.scatter"],
                           "ring.one_way": 2 * ms["ring.one_way"] + ms["ring.one_way.scatter"],
                           "ring.two_way": 2 * ms["ring.two_way"] + ms["ring.two_way.scatter"],
                           "tokens.all_to_all": 4 * ms["tokens.all_to_all"],
                           "tokens.ragged_all_to_all": 4 * ms["tokens.ragged_all_to_all"]}
    print(json.dumps(out))
    if not args.compile_only:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/moe_exchange_probe.json", "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    main()
