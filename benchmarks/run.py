"""Paper reproduction harness: one module per paper table/figure, plus the
roofline table, all on the CPU.  Prints ``name,us_per_call,derived`` CSV."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    import table1_fpu_summary
    import table2_comparison
    import fig2_latency_penalty
    import fig3_pareto
    import fig4_body_bias
    import roofline_table

    table1_fpu_summary.run()
    table2_comparison.run()
    fig2_latency_penalty.run()
    fig3_pareto.run()
    fig4_body_bias.run()
    roofline_table.run()


if __name__ == "__main__":
    main()
