SELECT id4, id5, approx_percentile_cont(v3, 0.5) AS median_v3, stddev(v3) AS stddev_v3 FROM x GROUP BY id4, id5
