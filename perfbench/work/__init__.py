"""Work counts: the operations and bytes the algorithm needs at the cell's
shapes, never those of an implementation, so that a PR which replaces a kernel
is read against the same work. `count(observed, **args) -> (flops, bytes)`
over the traced window, or None where there is nothing to count."""
