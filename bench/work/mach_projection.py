"""Work of the decode's projection (``MACHHead.meta_probs``) for one
batch: 2 operations per feature entry and output column, the feature
entries being d per dense query or nnz per CSR one.  The softmax is not
counted.  Bytes are not given: the projection has no roofline metric."""


def work(config: dict, traffic: dict) -> dict:
    n = traffic["queries_per_batch"]
    per_query = config["nnz"] if config["features"] == "csr" \
        else config["dim"]
    rb = config["num_repetitions"] * config["num_buckets"]
    return {"flops": 2 * n * per_query * rb}
