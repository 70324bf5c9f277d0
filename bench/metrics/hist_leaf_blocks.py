"""Leaf blocks of one tree fit: for each level (each distinct leaf count
the histogram kernel was traced at), how many blocks of leaves its call
splits into, each rebuilding the sample block's one-hot, summed over the
levels. Read from the program's
``mafl_kernel_tree_hist_leaf_blocks_total{n_leaves, blocks}`` counter in
this process's registry; nothing to read where the program has no such
counter or traced no kernel call."""


def read(obs):
    from repro.obs import metrics

    family = metrics.REGISTRY.get("mafl_kernel_tree_hist_leaf_blocks_total")
    if family is None:
        return None
    blocks = {}
    for child in family.children():
        if child.value > 0:
            labels = dict(child.labels)
            n_leaves, n = int(labels["n_leaves"]), int(labels["blocks"])
            blocks[n_leaves] = max(blocks.get(n_leaves, 0), n)
    return float(sum(blocks.values())) if blocks else None
