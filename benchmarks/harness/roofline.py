"""Operations and bytes of a kernel call, from its shapes.

The least time a call can take on a chip is the larger of
operations / peak FLOP/s and bytes / peak bytes/s; a kernel's roofline
share is that over its traced time.  Bytes are the algorithm's
compulsory traffic: every operand read once, every result written once.
"""

from __future__ import annotations


def conv_out(size: int, k: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - k) // stride + 1


def conv2d_call(n: int, h: int, w: int, cin: int, cout: int, k: int,
                stride: int, pad: int, act_bytes: int = 2,
                w_bytes: int = 2) -> dict:
    """One forward conv [n,h,w,cin] * [k,k,cin,cout] -> [n,oh,ow,cout]."""
    oh, ow = conv_out(h, k, stride, pad), conv_out(w, k, stride, pad)
    flops = 2.0 * n * oh * ow * k * k * cin * cout
    nbytes = (n * h * w * cin * act_bytes + k * k * cin * cout * w_bytes
              + n * oh * ow * cout * act_bytes)
    return {"flops": flops, "bytes": float(nbytes), "oh": oh, "ow": ow}


def conv2d_train(n, h, w, cin, cout, k, stride, pad, act_bytes: int = 2,
                 w_bytes: int = 2) -> dict:
    """Forward, input gradient and weight gradient of one conv: three
    contractions of the same size, each reading two operands and
    writing one, all in the activation/weight types given."""
    f = conv2d_call(n, h, w, cin, cout, k, stride, pad, act_bytes, w_bytes)
    x = n * h * w * cin * act_bytes
    y = n * f["oh"] * f["ow"] * cout * act_bytes
    wt = k * k * cin * cout * w_bytes
    return {"flops": 3.0 * f["flops"],
            "bytes": float((x + wt + y) + (y + wt + x) + (x + y + wt)),
            "oh": f["oh"], "ow": f["ow"]}


def min_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(least seconds on the chip, which bound binds)."""
    tc, tm = flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def calls_floor(rows: list[dict], peak: dict, train: bool = True) -> dict:
    """Sum over a family's calls (rows of n, h, w, cin, cout, k, stride,
    pad) of max(ops/peak, bytes/bandwidth)."""
    total, flops, nbytes, bound = 0.0, 0.0, 0.0, {"compute": 0, "memory": 0}
    for r in rows:
        kw = {k: r[k] for k in ("n", "h", "w", "cin", "cout", "k", "stride",
                                "pad")}
        c = conv2d_train(**kw) if train else conv2d_call(**kw)
        t, which = min_seconds(c["flops"], c["bytes"], peak)
        total += t
        flops += c["flops"]
        nbytes += c["bytes"]
        bound[which] += 1
    return {"seconds": total, "flops": flops, "bytes": nbytes,
            "bound": bound}


def paged_attention_bytes(context_lens, heads: int, head_dim: int,
                          layers: int, kv_bytes: int = 2) -> float:
    """K and V bytes one decode step must read: every live context once,
    in every layer."""
    return float(sum(context_lens)) * 2 * heads * head_dim * kv_bytes * layers
