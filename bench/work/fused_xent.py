"""Work of the fused MACH projection + cross-entropy, forward and
backward together, for one training step of the cell.

The op trains a linear head on data features: the forward takes the
logits z = x W + b (2 operations per feature entry and column) and the
per-head log-softmax; the backward forms dW = x^T dz (2 more) and
dbias.  No gradient of the features is part of it.  Bytes count each
input the math needs read once and each output written once as the op
returns it: the features, the rows of W they touch, bias and hashed
labels in; the per-example loss, a dense (d, R*B) dW and dbias out.
Recomputation, re-reads and blocking never count.

CSR features: the entries (N * nnz column ids and values) replace the
dense (N, d) block, and only the W rows they name are read (N * nnz
rows, an upper bound on the distinct ones).
"""

F32 = 4


def work(config: dict, traffic: dict) -> dict:
    n = traffic["examples_per_step"]
    d, r = config["dim"], config["num_repetitions"]
    rb = r * config["num_buckets"]
    if config["features"] == "csr":
        entries = n * config["nnz"]
        flops = 4 * entries * rb
        features = entries * (F32 + 4)
        w_read = entries * rb * F32
    else:
        flops = 4 * n * d * rb
        features = n * d * F32
        w_read = d * rb * F32
    bytes_ = (features + w_read + rb * F32 + n * r * 4     # in
              + n * F32 + d * rb * F32 + rb * F32)          # out
    return {"flops": flops, "bytes": bytes_}
