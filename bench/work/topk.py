"""Work of the streaming top-k decode (``kernels/mach_decode.py``) for
one batch: the unbiased estimator's R gathered adds for each of the N
queries and K classes; the (N, R, B) meta-probabilities and the (R, K)
hash table read once, the (N, k) values and ids written once.  Ranking
compares are not counted."""

F32 = 4


def work(config: dict, traffic: dict) -> dict:
    n, k = traffic["queries_per_batch"], traffic["k"]
    r, b, kk = (config["num_repetitions"], config["num_buckets"],
                config["num_classes"])
    return {"flops": n * kk * r,
            "bytes": n * r * b * F32 + r * kk * 4 + n * k * (F32 + 4)}
