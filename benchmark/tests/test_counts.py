"""The operation and byte counts against hand arithmetic, for one convolution
at the flagship fibers."""
from harness import counts

HIDDEN = [(0, 64), (1, 64), (2, 64), (3, 64)]
E = 1024 * 32


def _pairs():
    for d_out in range(4):
        for d_in in range(4):
            yield d_in, d_out, 2 * min(d_in, d_out) + 1


def test_conv_terms_match_hand_arithmetic():
    t = counts.conv_terms(HIDDEN, HIDDEN, E)
    # sum over the 16 degree pairs of F = 2 min + 1: 1*7 + 3*5 + 5*3 + 7*1
    sum_f = sum(f for _, _, f in _pairs())
    assert sum_f == 1 * 7 + 3 * 5 + 5 * 3 + 7 * 1 == 44
    assert t['radial_apply'] == 2 * E * 128 * 64 * 64 * sum_f
    # sum of P*F and of P*Q*F over the pairs
    sum_pf = sum((2 * do + 1) * f for _, do, f in _pairs())
    sum_pqf = sum((2 * do + 1) * (2 * di + 1) * f for di, do, f in _pairs())
    assert sum_pqf == 1092
    assert t['out_contract'] == 2 * E * 64 * 64 * sum_pf
    assert t['basis_contract'] == 2 * E * 64 * sum_pqf
    assert t['trunk'] == 2 * E * 2 * 128 * 128
    # the radial apply is the bulk of a convolution
    assert t['radial_apply'] / sum(t.values()) > 0.7


def test_training_step_is_three_forwards_and_kernels_are_part_of_it():
    model = dict(dim=64, depth=6, num_degrees=4, heads=8, dim_head=8,
                 num_neighbors=32, output_degrees=2)
    fwd = counts.forward_flops(model, 1024)
    assert counts.train_step_flops(model, 1024) == 3 * fwd
    k_fwd = counts.kernel_flops(model, 1024, backward=False)
    k_all = counts.kernel_flops(model, 1024, backward=True)
    assert 0.9 * fwd < k_fwd < fwd          # the kernels are ~all of it
    assert k_fwd < k_all < 3 * k_fwd        # and no replay is counted
    # 2 convolutions a block, 6 blocks, plus conv_in and conv_out
    assert len(counts._convs(model)) == 14


def test_one_headed_keys_and_values_have_one_head_of_channels():
    model = dict(dim=64, depth=4, num_degrees=4, heads=8, dim_head=24,
                 one_headed_key_values=True, num_neighbors=32,
                 output_degrees=2)
    _, hidden, q, kv, _ = counts.model_shapes(model)
    assert q == [(d, 192) for d in range(4)]
    assert kv == [(d, 24) for d in range(4)]
    # the radial apply of one key convolution: 44 degree-pair frequencies
    t = counts.conv_terms(hidden, kv, E)
    assert t['radial_apply'] == 2 * E * 128 * 64 * 24 * 44
    full = counts.forward_flops(dict(model, one_headed_key_values=False),
                                1024)
    assert counts.forward_flops(model, 1024) < 0.3 * full


def test_kernel_bytes_count_each_operand_once():
    model = dict(dim=64, depth=0, num_degrees=1, heads=8, dim_head=8,
                 num_neighbors=32, output_degrees=1)
    # conv_in and conv_out, degree 0 -> 0 only: P = Q = F = 1
    e = 1024 * 32
    w = 128 * 64 * 64 + 64 * 64
    one = 4 * (e * (128 + 1 + 64) + w + e * 64)
    assert counts.kernel_bytes(model, 1024, backward=False) == 2 * one
