"""Control for symmetric 2pc cells, whose guarantee is that a state stands
for its permutations and no others: sort the resource managers by their
state but leave each one's `tm_prepared` flag and `Prepared` message at the
old position.

The broken sort (`TwoPhaseTensor.representative_lanes`, in the device
program and in the host's path replay alike) ties a flag to whichever
resource manager lands in its slot: the step that would drop two of the
three lanes from the sort. States that are no permutation of each other
then share a representative, and representatives are no longer closed
under the reference's rule, so the count and the paths part from it. No
choice depends on the seed.
"""

from __future__ import annotations


def install(seed: int) -> None:
    from stateright_tpu.models import TwoPhaseTensor

    def detached(self, xp, lanes):
        n = self.n
        u = xp.uint32
        lane0, lane1, lane2 = lanes
        keys = [(((lane1 >> u(2 * i)) & u(3)) << u(4)) | u(i) for i in range(n)]
        for p in range(n):
            for m in range(p & 1, n - 1, 2):
                lo = xp.minimum(keys[m], keys[m + 1])
                hi = xp.maximum(keys[m], keys[m + 1])
                keys[m], keys[m + 1] = lo, hi
        new1 = lane1 & ~u((1 << (2 * n)) - 1)
        for j, k in enumerate(keys):
            new1 = new1 | (((k >> u(4)) & u(3)) << u(2 * j))
        return (lane0, new1, lane2)

    TwoPhaseTensor.representative_lanes = detached
