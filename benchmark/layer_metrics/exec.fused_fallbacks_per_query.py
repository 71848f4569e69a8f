"""Operator layer: exec:fused-fallback spans per request of the window:
partitions a fused stage re-ran through the unfused operator chain (a join
build that refused, a carry that overflowed, an empty partition). Expected
0. Counted over the requests whose trace holds an exec:join-build span: a
program that records the one records the other when it falls back, so such
a request without a fallback span reads 0, and a program that records
neither (the parent of the PR that added them) reads nothing. The gauge
fused_fallback_total would count set-up's and the warm rounds' too."""
import spantree


def read(ctx):
    return spantree.mean(spantree.per_request(
        ctx, "exec:join-build",
        lambda t: len(spantree.named(t, "exec:fused-fallback"))))
