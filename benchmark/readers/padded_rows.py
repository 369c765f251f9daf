"""Share of the rows the rounds carried that were padding: 1 - real rows /
packed rows, in per cent. Real rows are the benchmark's own count from the
federation's sizes; packed rows are the cohort's slots times the padded
length the program's packer policy gives that cohort."""


def read(ctx):
    packed = ctx.counts["packed_rows"]
    return 100.0 * (1.0 - ctx.counts["real_rows"] / packed)
